"""e2f_p99_ms — served path: 99th percentile of sink-visible minus due."""

from benchlib import observe


def read(obs):
    return observe.e2f_percentile(obs, 99)
