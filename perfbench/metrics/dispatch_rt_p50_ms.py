"""dispatch_rt_p50_ms — dispatch: median device.roundtrip span."""

from benchlib import observe


def read(obs):
    return observe.span_p50_ms(obs, 'device.roundtrip')
