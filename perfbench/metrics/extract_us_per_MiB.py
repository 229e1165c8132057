"""extract_us_per_MiB — kernels: device time of the _extract* operations per MiB of padded rows."""

from benchlib import observe


def read(obs):
    return observe.extract_us_per_MiB(obs)
