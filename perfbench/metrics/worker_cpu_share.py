"""worker_cpu_share — host: CPU seconds of the busiest processor-N thread between the two scrapes of
/debug/status threads (the kernel's account of that thread), as a share of the window (the scrapes
also bracket the drain and a traced run's profiler stop, so the seconds go with the bytes: the
window's part of the bytes delivered between the scrapes, over the window's seconds): 1.0 is a worker
bound by its own work.  Read in every run's scrapes, traced or not.  Says, on standard error,
the whole threads difference: every named thread's CPU seconds, run-queue wait, switches, the
CPU it ran on last at both ends, and the sum over the process's other tasks.  Nothing on a
program without the section."""

from benchlib import threads


def read(obs):
    threads.say_threads(obs)
    return threads.thread_share(obs, threads.WORKER_PREFIX, "cpu_s")
