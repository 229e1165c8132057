"""worker_runq_share — host: seconds the busiest processor-N thread was runnable and kept off a CPU
by the host (schedstat's run-queue wait) between the two scrapes of /debug/status threads, as a
share of the window (as worker_cpu_share takes it): the signature of a slow second that is the
shared host's and not the program's.  Nothing where the kernel gives no schedstat, or on a program without the section.
Reported as worker_runq_share.sat and worker_runq_share.tail."""

from benchlib import threads


def read(obs):
    return threads.thread_share(obs, threads.WORKER_PREFIX, "runq_wait_s")
