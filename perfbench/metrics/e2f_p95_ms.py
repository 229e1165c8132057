"""e2f_p95_ms — 95th percentile, over every line due in the window, of sink-visible time
minus the time the line's write was DUE; a line that never settled counts as
infinitely late."""

from benchlib import observe


def read(obs):
    return observe.e2f_percentile(obs, 95)
