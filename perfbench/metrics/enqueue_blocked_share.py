"""enqueue_blocked_share — serialize / sink: seconds the worker waited at a sender's full FIFO
(/debug/status flush, enqueue_blocked_seconds summed over the sinks, later less earlier), as a
share of the window (as worker_cpu_share takes it).  0 where the sender keeps up.  Nothing on a program without
the counter."""

from benchlib import threads


def read(obs):
    return threads.enqueue_blocked_share(obs)
