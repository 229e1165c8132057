"""proc_stage_cpu_s_per_GB — processors: self CPU seconds of the processor.* spans (each span's
cpu_s less its children's on the same thread) per GB delivered in the traced slice: the work
inside proc_stage_s_per_GB.sat, whose seconds also hold what the worker waited for the
interpreter lock.  Nothing on a program whose spans carry no cpu_s."""

from benchlib import observe, threads


def read(obs):
    return observe.per_GB(obs, threads.self_cpu_seconds(obs, "processor."), True)
