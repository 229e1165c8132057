"""delivered_MBps — Input bytes (lines with their newline, MB = 10^6) whose fate was settled in
the sink inside the window, over the window's seconds.  One number for the
whole window."""

from benchlib import observe


def read(obs):
    return observe.delivered_bytes(obs) / 1e6 / (obs['t1'] - obs['t0'])
