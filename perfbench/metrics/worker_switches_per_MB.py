"""worker_switches_per_MB — host: voluntary context switches of the busiest processor-N thread
between the two scrapes of /debug/status threads, over the input MB delivered between them: how
often the worker let go of its CPU (a wait for the interpreter lock, the device or a queue) for
the work it did — "few calls that let go of the lock, not few operations" as a number.  Nothing
on a program without the section."""

from benchlib import threads


def read(obs):
    return threads.worker_switches_per_MB(obs)
