"""json_program_roofline — kernels: bytes the calls of the program that carries the json_fields
stage had to move, by their shapes, over the chip's HBM peak, as a percentage of the program's
device time (its XLA Modules events in the traced slice).  Bound: hbm.  The shapes are the
program's own account of itself (/debug/status stage_fusion programs: one geometry, the span
columns each stage publishes); nothing where the program has no such stage, ran more than one
geometry (a call's shape is then not known), or no call fell in the slice."""

from benchlib import roofline, spans, tracered

MODULE = "jit_loong_fused_program"


def call_bytes(rows: int, width: int, captures: int, keeps: int) -> int:
    """Bytes one call must move: the padded rows and a length per row in; out, the stage's ok
    byte, an (offset, length) pair of 32-bit words per span column, status, member count and
    the two signature words per row, and a byte per row for each keep mask."""
    stage_out = 1 + 8 * captures + 4 + 4 + 8
    return rows * width + 4 * rows + rows * stage_out + rows * keeps


def read(obs):
    tr = obs.get("trace")
    programs = ((obs["status1"] or {}).get("stage_fusion") or {}).get("programs") or []
    mine = [p for p in programs
            if any(s.startswith("json_fields") for s in p.get("stages", []))]
    if not tr or len(mine) != 1 or len(mine[0].get("geometries", [])) != 1 \
            or "captures" not in mine[0]:
        return None
    rows, width = (int(x) for x in mine[0]["geometries"][0].split("x"))
    at = [i for i, s in enumerate(mine[0]["stages"]) if s.startswith("json_fields")][0]
    per_call = call_bytes(rows, width, mine[0]["captures"][at],
                          sum(s == "filter" for s in mine[0]["stages"]))
    calls = [float(dur) / 1e9 for plane, line, name, start, dur in tr["events"]
             if plane.startswith(tracered.DEVICE_PLANE) and line == spans.MODULES_LINE
             and name.startswith(MODULE) and tr["lo_ns"] <= float(start) < tr["hi_ns"]]
    if not calls:
        return None
    spans.say("json program: calls in the slice, bytes a call, device seconds",
              [len(calls), per_call, sum(calls)])
    peak = roofline.peak_of(obs["peaks"], obs["device"]["kind"])
    return roofline.hbm_roofline_pct(len(calls) * per_call, sum(calls), peak)
