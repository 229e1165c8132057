"""read_stage_s_per_GB — file input: input.file.read spans (pread, newline align, presplit)
per GB delivered in the traced slice."""

from benchlib import spans


def read(obs):
    return spans.per_GB_in_slice(obs, ("input.file.read",))
