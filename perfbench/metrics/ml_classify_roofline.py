"""ml_classify_roofline — kernels: bytes the calls of the start-pattern classify program had to
move, by their shapes, over the chip's HBM peak, as a percentage of the program's device time
(its XLA Modules events in the traced slice).  Bound: hbm.  The shapes are the program's own
account (/debug/status multiline classify_calls: calls by geometry, between the two scrapes);
where the window ran more than one geometry a call of the slice is taken at their mean by
calls, and the line says so.  Nothing on a program without the section or the module, or with
no call in the slice."""

from benchlib import roofline, spans, tracered

MODULE = "jit_loong_line_classify"


def call_bytes(rows: int, width: int) -> int:
    """Bytes one call must move: the padded rows and a length per row in, one 32-bit result
    word a row out."""
    return rows * width + 4 * rows + 4 * rows


def _calls(status) -> dict:
    out: dict = {}
    for row in ((status or {}).get("multiline") or {}).values():
        for geometry, n in (row.get("classify_calls") or {}).items():
            out[geometry] = out.get(geometry, 0) + int(n)
    return out


def read(obs):
    tr = obs.get("trace")
    later = _calls(obs.get("status1"))
    if not tr or not later:
        return None
    earlier = _calls(obs.get("status0"))
    window = {g: n - earlier.get(g, 0) for g, n in later.items() if n > earlier.get(g, 0)}
    if not window:
        return None
    per_call = sum(call_bytes(*(int(x) for x in g.split("x"))) * n
                   for g, n in window.items()) / sum(window.values())
    calls = [float(dur) / 1e9 for plane, line, name, start, dur in tr["events"]
             if plane.startswith(tracered.DEVICE_PLANE) and line == spans.MODULES_LINE
             and name.startswith(MODULE) and tr["lo_ns"] <= float(start) < tr["hi_ns"]]
    if not calls:
        return None
    spans.say("classify program: calls in the slice, bytes a call, device seconds, "
              "the window's calls by geometry", [len(calls), per_call, sum(calls), window])
    peak = roofline.peak_of(obs["peaks"], obs["device"]["kind"])
    return roofline.hbm_roofline_pct(len(calls) * per_call, sum(calls), peak)
