"""queue_wait_p50_ms — queue / runner: median of the process queue's wait histogram over the
window (the program's log2 buckets: the value is a bucket's upper bound).
Reported as queue_wait_p50_ms.sat and queue_wait_p50_ms.tail."""

from benchlib import observe


def read(obs):
    return observe.queue_wait_p50_ms(obs)
