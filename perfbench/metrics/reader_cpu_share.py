"""reader_cpu_share — file input: CPU seconds of the file-server thread between the two scrapes of
/debug/status threads, as a share of the window (as worker_cpu_share takes it).  Highest where the reader is the limit (a worker that
finds its queue empty).  Nothing on a program without the section."""

from benchlib import threads


def read(obs):
    return threads.thread_share(obs, threads.READER_THREAD, "cpu_s")
