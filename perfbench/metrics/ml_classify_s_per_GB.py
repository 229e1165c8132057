"""ml_classify_s_per_GB — processors: self time of the multiline.classify* spans (the
start-pattern classify's dispatch and complete legs, or the whole classify where it runs in one
piece) per GB delivered in the traced slice.  Self time: the device legs that nest under them
(device.pack / submit / wait / d2h) are their own metrics, subtracted here as
proc_stage_s_per_GB subtracts them from the stage spans.  Nothing on a program without the
spans."""

from benchlib import observe, tracered

PREFIX = "multiline.classify"


def read(obs):
    spans = obs.get("spans") or []
    if not any(s[0].startswith(PREFIX) for s in spans):
        return None
    by = tracered.self_seconds(spans)
    return observe.per_GB(obs, sum(v for k, v in by.items() if k.startswith(PREFIX)), True)
