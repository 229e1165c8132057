"""e2f_p50_ms — served path: median of sink-visible minus due."""

from benchlib import observe


def read(obs):
    return observe.e2f_percentile(obs, 50)
