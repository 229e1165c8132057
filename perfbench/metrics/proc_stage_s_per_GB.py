"""proc_stage_s_per_GB — processors: self time of the processor.* spans per GB delivered in the
traced slice.  Reported as proc_stage_s_per_GB.sat."""

from benchlib import observe


def read(obs):
    return observe.per_GB(obs, observe.span_seconds(obs, 'processor.', True), True)
