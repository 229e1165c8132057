"""device_program_s_per_GB — kernels: device seconds of the jit_loong_* modules (the names the
program gave its jitted programs; the profiler XLA Modules line) per GB delivered in the
traced slice."""

from benchlib import observe, spans


def read(obs):
    found = spans.module_seconds(obs, "jit_loong_")
    if found is None:
        return None
    spans.say("device programs in the slice (XLA Modules, seconds)", found[1])
    return observe.per_GB(obs, found[0], True)
