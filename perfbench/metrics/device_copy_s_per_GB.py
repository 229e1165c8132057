"""device_copy_s_per_GB — dispatch: the host moving bytes to and from the device (device.pack,
device.submit and device.d2h spans) per GB delivered in the traced slice."""

from benchlib import spans


def read(obs):
    return spans.per_GB_in_slice(obs, ("device.pack", "device.submit", "device.d2h"))
