"""fused_dispatch_share — dispatch: device dispatches served by a fused pipeline program, over all."""

from benchlib import observe


def read(obs):
    return observe.fused_dispatch_share(obs)
