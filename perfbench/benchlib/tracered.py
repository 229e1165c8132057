"""From a profiler trace and the program's spans to numbers.

The trace arrives as events ``[plane, line, name, start_ns, duration_ns]``
(``xplane_dump.py`` writes them from the profiler's .xplane.pb); the spans as
``[name, start_s, seconds, id, parent_id, attrs]`` on the perf_counter clock
(``launcher.py`` writes them).  The launcher's mark sits in both, which puts
them on one clock.
"""

from __future__ import annotations

import re

import numpy as np

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
MARK = "perfbench_mark"


def short_name(op_text: str) -> str:
    """``%extract.1 = (...) custom-call(...)`` → ``_extract.1``."""
    lhs = op_text.split(" = ", 1)[0].strip()
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", lhs)[:64]


def device_ops(events) -> list:
    """The operations that ran on a device: (plane, name, start_ns, dur_ns)."""
    return [(e[0], e[2], float(e[3]), float(e[4])) for e in events
            if e[0].startswith(DEVICE_PLANE) and e[1] == OPS_LINE]


def mark_start_ns(events):
    for e in events:
        if e[2] == MARK:
            return float(e[3])
    return None


def union_intervals(starts, durs):
    """Merged (start, end) intervals, as two arrays."""
    if len(starts) == 0:
        return np.empty(0), np.empty(0)
    order = np.argsort(starts)
    s = np.asarray(starts, np.float64)[order]
    e = s + np.asarray(durs, np.float64)[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], reach[last]


def busy_seconds(ops, lo_ns: float, hi_ns: float) -> float:
    """Seconds inside [lo, hi) in which an operation ran, averaged over the
    device planes that appear."""
    planes = sorted({o[0] for o in ops})
    if not planes:
        return 0.0
    total = 0.0
    for p in planes:
        s, e = union_intervals([o[2] for o in ops if o[0] == p],
                               [o[3] for o in ops if o[0] == p])
        total += float(np.sum(np.clip(np.minimum(e, hi_ns)
                                      - np.maximum(s, lo_ns), 0, None)))
    return total / len(planes) / 1e9


def op_seconds(ops) -> dict:
    """Device seconds by short operation name."""
    out: dict = {}
    for _plane, name, _start, dur in ops:
        k = short_name(name)
        out[k] = out.get(k, 0.0) + dur / 1e9
    return out


def kernel_calls(ops, prefix: str) -> list:
    """(op text, seconds) of every call whose short name starts ``prefix``."""
    return [(name, dur / 1e9) for _p, name, _s, dur in ops
            if short_name(name).startswith(prefix)]


def self_seconds(spans) -> dict:
    """Self time by span name: a span's seconds less what its children cover
    (children are summed, clipped to the parent)."""
    child = {}
    for name, start, dur, sid, parent, _attrs in spans:
        if parent is not None:
            child[parent] = child.get(parent, 0.0) + dur
    out: dict = {}
    for name, start, dur, sid, parent, _attrs in spans:
        out[name] = out.get(name, 0.0) + max(dur - child.get(sid, 0.0), 0.0)
    return out


def idle_gaps_by_span(ops, spans, lo_s: float, hi_s: float,
                      to_seconds) -> dict:
    """Device idle seconds inside [lo_s, hi_s) by what the host was doing:
    every instant of a gap goes to the host span open at that instant that
    started last (the innermost on its thread), or to ``_no_span_`` when none
    was open.  ``to_seconds`` maps a trace timestamp (ns) onto the spans'
    clock."""
    s, e = union_intervals([o[2] for o in ops], [o[3] for o in ops])
    s, e = to_seconds(s), to_seconds(e)
    gap_lo = np.clip(np.concatenate([[lo_s], e]), lo_s, hi_s)
    gap_hi = np.clip(np.concatenate([s, [hi_s]]), lo_s, hi_s)
    keep = gap_hi > gap_lo
    gaps = list(zip(gap_lo[keep].tolist(), gap_hi[keep].tolist()))
    # one sweep over the spans' starts and ends and the gaps' edges
    points = []
    for i, sp in enumerate(spans):
        a, b = sp[1], sp[1] + sp[2]
        if b > lo_s and a < hi_s:
            points.append((a, 1, i))
            points.append((b, 0, i))
    for a, b in gaps:
        points.append((a, 2, -1))
        points.append((b, 2, -1))
    points.sort()
    out: dict = {}
    open_spans: dict = {}
    g = 0
    prev = lo_s
    for t, kind, i in points:
        t = min(max(t, lo_s), hi_s)
        while g < len(gaps) and gaps[g][1] <= prev:
            g += 1
        if t > prev and g < len(gaps) and gaps[g][0] <= prev \
                and t <= gaps[g][1] + 1e-12:
            owner = spans[max(open_spans, key=open_spans.get)][0] \
                if open_spans else "_no_span_"
            out[owner] = out.get(owner, 0.0) + (t - prev)
        prev = max(prev, t)
        if kind == 1:
            open_spans[i] = spans[i][1]
        elif kind == 0:
            open_spans.pop(i, None)
    return out


def top(doc: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(doc.items(), key=lambda kv: -kv[1])[:n]]
