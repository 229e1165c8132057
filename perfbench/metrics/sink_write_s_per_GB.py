"""sink_write_s_per_GB — serialize / sink: flusher.write spans per GB delivered in the traced
slice."""

from benchlib import spans


def read(obs):
    return spans.per_GB_in_slice(obs, ("flusher.write",))
