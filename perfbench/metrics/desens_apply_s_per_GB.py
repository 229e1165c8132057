"""desens_apply_s_per_GB — processors: seconds in the desensitize.apply spans (the fused program's
mask pairs turned into the masked url values: one native call a group writes every changed value into
one arena block, one set_field installs them; the host tier's rows of the group, where there are any)
per GB delivered in the traced slice.  A child of the fused chain's complete span, so
proc_stage_s_per_GB.sat does not hold it.  Nothing on a program without the span."""

from benchlib import spans


def read(obs):
    return spans.per_GB_in_slice(obs, total=("desensitize.apply",))
