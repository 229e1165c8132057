"""throttled_share — governor: share of the window's one-second CPU samples over 0.7 of
cpu_usage_limit."""

from benchlib import observe


def read(obs):
    return observe.throttled_share(obs)
