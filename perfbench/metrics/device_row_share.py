"""device_row_share — routing: rows that crossed the device over those and the rows routing kept
on the host tiers, in the window.  Reported as device_row_share.sat (moves
delivered_MBps) and device_row_share.tail (moves e2f_p95_ms)."""

from benchlib import observe


def read(obs):
    return observe.device_row_share(obs)
