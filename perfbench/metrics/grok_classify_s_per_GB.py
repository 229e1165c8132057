"""grok_classify_s_per_GB — processors: seconds in the grok.classify spans (the Match list's one
classify pass over a group's rows: the fused automaton's scan and the member masks, or the
per-pattern probe where a list does not fuse) per GB delivered in the traced slice.  A child of
the stage's dispatch span, so proc_stage_s_per_GB.sat does not hold it.  Nothing on a program
without the span."""

from benchlib import spans


def read(obs):
    return spans.per_GB_in_slice(obs, total=("grok.classify",))
