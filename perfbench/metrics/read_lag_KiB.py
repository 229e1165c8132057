"""read_lag_KiB — file input: median, over the window's polls of /debug/ledger, of bytes
written less bytes the reader had taken in."""

import numpy as np

from benchlib import observe


def read(obs):
    lag = observe.read_lag_bytes(obs)
    return None if lag is None else float(np.median(lag)) / 1024
