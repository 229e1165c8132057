"""first_dispatch_s — start-up: from the pipelines started to the first device dispatch
materialised (routing probe, compile or cache load, first round trip), /debug/status startup."""

from benchlib import spans


def read(obs):
    return spans.startup_gap(obs, "first_dispatch", "pipelines_started")
