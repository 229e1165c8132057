"""device_idle_share — device: 1 - union of device-operation intervals over the traced window."""

from benchlib import observe


def read(obs):
    return observe.device_idle_share(obs)
