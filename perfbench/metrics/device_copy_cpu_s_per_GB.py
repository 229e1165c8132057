"""device_copy_cpu_s_per_GB — dispatch: CPU seconds of the device.pack, device.submit and
device.d2h spans per GB delivered in the traced slice: the work inside device_copy_s_per_GB (a
synchronous transfer is mostly waiting).  Nothing on a program whose spans carry no cpu_s."""

from benchlib import observe, threads


def read(obs):
    return observe.per_GB(obs, threads.cpu_seconds_of(
        obs, ("device.pack", "device.submit", "device.d2h")), True)
