"""classify_apply_s_per_GB — processors: seconds in the classify.apply spans (the fused program's
label column turned into the category field: one table read a row, one set_field a group, the
counters) per GB delivered in the traced slice.  A child of the fused chain's complete span, so
proc_stage_s_per_GB.sat does not hold it.  Nothing on a program without the span."""

from benchlib import spans


def read(obs):
    return spans.per_GB_in_slice(obs, total=("classify.apply",))
