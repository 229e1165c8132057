"""sink_flush_KiB_p50 — serialize / sink: median size of what one read of the tailer found new in
the sink (a flush of the batcher, where the tailer keeps up)."""

from benchlib import observe


def read(obs):
    return observe.sink_flush_KiB_p50(obs)
