"""device_wait_s_per_GB — dispatch: the host blocked on the device (device.wait spans) or on
the in-flight budget (self time of device.acquire spans: what it drains meanwhile is a
device.wait of its own) per GB delivered in the traced slice."""

from benchlib import spans


def read(obs):
    found = spans.makeup(obs, ".complete")
    if found is not None:
        spans.say("the .complete stages in the slice (seconds)", found)
    return spans.per_GB_in_slice(obs, ("device.wait",), ("device.acquire",))
