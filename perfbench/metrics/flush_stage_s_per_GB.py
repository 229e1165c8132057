"""flush_stage_s_per_GB — serialize / sink: time in flusher.send spans per GB delivered."""

from benchlib import observe


def read(obs):
    return observe.per_GB(obs, observe.span_seconds(obs, 'flusher.send', False), True)
