"""backend_init_s — start-up: from the agent main() entered (imports done) to its device
backend up (jax import and the TPU client start), /debug/status startup."""

from benchlib import spans


def read(obs):
    return spans.startup_gap(obs, "backend_up", "imports_done")
