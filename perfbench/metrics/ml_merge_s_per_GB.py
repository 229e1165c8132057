"""ml_merge_s_per_GB — processors: seconds in the multiline.merge spans (block walk over the
classified lines, carry stitching, emit of the merged records) per GB delivered in the traced
slice.  Nothing on a program without the span."""

from benchlib import spans


def read(obs):
    return spans.per_GB_in_slice(obs, total=("multiline.merge",))
