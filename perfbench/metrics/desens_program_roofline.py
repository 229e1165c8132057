"""desens_program_roofline — kernels: bytes the calls of the program that carries the mask stage had
to move, by their shapes, over the chip's HBM peak, as a percentage of the program's device time
(its XLA Modules events in the traced slice).  Bound: hbm.  The shapes are the program's own account
of itself (/debug/status stage_fusion programs: the span columns and the mask slots each stage
publishes, and its dispatches by geometry at both scrapes — the shape of the window's calls is the
geometry nearly all of them had: a 512 KiB group of 512-byte lines is one 1024 x 512 call); the work
is kept here, so a rewritten walk reads against the same bytes.  Nothing where no program has such a
stage, where a call's shape is not known (classify_program_roofline's rule), or where no call fell
in the slice."""

from benchlib import roofline, spans, tracered

MODULE = "jit_loong_fused_program"
STAGE = "mask:"


def call_bytes(rows: int, width: int, captures: int, slots: int) -> int:
    """Bytes one call must move: the padded rows and a length per row in; out, the extract's ok
    byte, an (offset, length) pair of 32-bit words per capture, a (start, end) pair of 32-bit words
    per mask slot and the 32-bit match count per row."""
    return rows * width + 4 * rows + rows * (1 + 8 * captures + 8 * slots + 4)


def _masking(status):
    programs = ((status or {}).get("stage_fusion") or {}).get("programs") or []
    return [p for p in programs if any(s.startswith(STAGE) for s in p.get("stages", []))]


def geometry_of_the_window(obs):
    """"<rows>x<width>" of the window's calls, or None where it is not known."""
    mine = _masking(obs.get("status1"))
    if len(mine) != 1 or "captures" not in mine[0] or "slots" not in mine[0]:
        return None
    later = mine[0].get("geometry_dispatches")
    if later is None:
        shapes = mine[0].get("geometries", [])
        return shapes[0] if len(shapes) == 1 else None
    earlier = [p for p in _masking(obs.get("status0"))
               if p.get("signature") == mine[0].get("signature")]
    before = (earlier[0].get("geometry_dispatches") or {}) if earlier else {}
    calls = {g: n - before.get(g, 0) for g, n in later.items()}
    top = max(calls, key=calls.get, default=None)
    if top is None or calls[top] <= 0 or calls[top] < 0.99 * sum(calls.values()):
        return None
    return top


def read(obs):
    tr = obs.get("trace")
    shape = geometry_of_the_window(obs) if tr else None
    if shape is None:
        return None
    rows, width = (int(x) for x in shape.split("x"))
    program = _masking(obs["status1"])[0]
    per_call = call_bytes(rows, width, sum(program["captures"]), sum(program["slots"]))
    calls = [float(dur) / 1e9 for plane, line, name, start, dur in tr["events"]
             if plane.startswith(tracered.DEVICE_PLANE) and line == spans.MODULES_LINE
             and name.startswith(MODULE) and tr["lo_ns"] <= float(start) < tr["hi_ns"]]
    if not calls:
        return None
    spans.say("mask program: calls in the slice, bytes a call, device seconds",
              [len(calls), per_call, sum(calls)])
    peak = roofline.peak_of(obs["peaks"], obs["device"]["kind"])
    return roofline.hbm_roofline_pct(len(calls) * per_call, sum(calls), peak)
