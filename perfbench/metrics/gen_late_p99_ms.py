"""gen_late_p99_ms — generator: 99th percentile of write done minus write due."""

from benchlib import observe


def read(obs):
    return observe.gen_late_p99_ms(obs)
