"""From a traffic file to the writes of a window.

One general generator serves every open-loop mix; a mix is its parameters:

    mode          "open" (writes are due on a schedule, whatever the agent
                  does) or "closed" (the file is kept ``lead_MiB`` ahead of
                  what the sink has settled; no schedule)
    write_lines   lines per write() call
    arrivals      open only: "exponential" (``rate_MBps``, gaps between writes
                  drawn from an exponential distribution) or "burst" (every
                  ``period_s`` seconds ``burst_MB`` megabytes, all due at the
                  period's start, written as fast as the generator can)

Every seed gets the same SET of gaps in another order: the n gaps are the
mid-quantiles of the exponential distribution, permuted by the seed, so two
seeds offer exactly the same work and differ only in its order.
"""

from __future__ import annotations

import numpy as np


def open_schedule(traffic: dict, seconds: float, line_bytes: int, seed: int):
    """``(due, n_lines)``: when each write is due (seconds from the window's
    start) and how many lines it carries."""
    per_write = int(traffic["write_lines"])
    write_bytes = per_write * line_bytes
    arrivals = traffic["arrivals"]
    if arrivals == "exponential":
        n = int(round(float(traffic["rate_MBps"]) * 1e6 * seconds / write_bytes))
        mean = seconds / n
        gaps = -mean * np.log1p(-(np.arange(n) + 0.5) / n)
        np.random.default_rng(seed).shuffle(gaps)
        due = np.cumsum(gaps)
        # the mid-quantile gaps sum to a hair under n*mean; keep it inside
        due *= min(1.0, (seconds - mean / 2) / due[-1])
    elif arrivals == "burst":
        period = float(traffic["period_s"])
        periods = int(seconds // period)
        if periods < 1:
            raise ValueError(f"a window of {seconds} s holds no period of "
                             f"{period} s")
        writes = int(round(float(traffic["burst_MB"]) * 1e6 / write_bytes))
        due = np.repeat(np.arange(periods) * period, writes)
    else:
        raise ValueError(f"unknown arrivals {arrivals!r}")
    n_lines = np.full(due.size, per_write, np.int64)
    return due, n_lines
