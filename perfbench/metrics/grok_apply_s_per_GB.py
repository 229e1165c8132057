"""grok_apply_s_per_GB — processors: seconds in the grok.apply spans (the members' capture spans
written into the group's columns in Match order, rawLog for the rows no member matched) per GB
delivered in the traced slice.  A child of the stage's complete span (or of its dispatch span
where every member's subset finished at dispatch), so proc_stage_s_per_GB.sat does not hold it.
Nothing on a program without the span."""

from benchlib import spans


def read(obs):
    return spans.per_GB_in_slice(obs, total=("grok.apply",))
