"""pause_over_32ms — host: how many of those spans took longer than 32.768 ms (the log2
bucket edge), between the two scrapes."""

from benchlib import spans


def read(obs):
    return spans.pauses_over(obs, 0.032768)
