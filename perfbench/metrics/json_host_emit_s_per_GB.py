"""json_host_emit_s_per_GB — processors: seconds in the json.host_emit spans (the native emitter
over the rows the device stage handed back, under processor.fused_chain.complete) per GB
delivered in the traced slice.  Nothing on a program without the span."""

from benchlib import spans


def read(obs):
    return spans.per_GB_in_slice(obs, total=("json.host_emit",))
