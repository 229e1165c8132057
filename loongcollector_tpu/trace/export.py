"""Trace → self-telemetry conversion + the unified timeline export.

Reference shape: core/monitor/SelfMonitorServer.cpp converts metric
records and alarms into PipelineEventGroups pushed into INTERNAL
pipelines; traces ride the same dogfooding path — every finished span and
timeline event becomes a log event tagged ``__source__ = loongtrace``, so
an operator's sink sees a breaker trip, the chaos injection that caused
it, and the resulting spill as rows of one queryable stream.

loongxprof adds :func:`chrome_trace`: the host spans (loongtrace) and the
per-dispatch device legs (ops/xprof DeviceTimeline) merged into one
Chrome-trace JSON object — loadable in Perfetto / chrome://tracing —
correlated per dispatch id and aligned on a single perf_counter clock
(Span._start_perf and DeviceTimeline.epoch read the same counter).
:func:`canonicalize` reduces that document to its timing-independent
structure so two runs of the same seeded storm compare byte-identical,
exactly like ``Tracer.structure_bytes``.
"""

from __future__ import annotations

import json
from typing import List, Optional

from ..models import PipelineEventGroup
from .tracer import _VOLATILE_ATTRS, VOLATILE_SPANS, Span, TraceEvent


def _put(ev, sb, key: str, value: str) -> None:
    ev.set_content(sb.copy_string(key), sb.copy_string(value))


def traces_to_group(spans: List[Span],
                    events: List[TraceEvent]) -> Optional[PipelineEventGroup]:
    """One event group carrying a drained trace batch; None when empty."""
    if not spans and not events:
        return None
    group = PipelineEventGroup()
    sb = group.source_buffer
    for span in spans:
        ev = group.add_log_event(int(span.start_wall))
        _put(ev, sb, "kind", "span")
        _put(ev, sb, "name", span.name)
        _put(ev, sb, "trace_id", span.trace_id)
        _put(ev, sb, "span_id", str(span.span_id))
        if span.parent_id is not None:
            _put(ev, sb, "parent_id", str(span.parent_id))
        _put(ev, sb, "status", span.status)
        if span.duration_s is not None:
            _put(ev, sb, "duration_ms",
                 f"{span.duration_s * 1000.0:.3f}")
        if span.attrs:
            _put(ev, sb, "attrs", json.dumps(span.attrs, sort_keys=True,
                                             default=str))
        if span.events:
            _put(ev, sb, "events", json.dumps(
                [{"name": n, "t_ms": round(dt * 1000.0, 3), **a}
                 for n, dt, a in span.events],
                sort_keys=True, default=str))
    for tev in events:
        ev = group.add_log_event(int(tev.wall))
        _put(ev, sb, "kind", "event")
        _put(ev, sb, "name", tev.name)
        _put(ev, sb, "seq", str(tev.seq))
        if tev.span_id is not None:
            _put(ev, sb, "span_id", str(tev.span_id))
        if tev.attrs:
            _put(ev, sb, "attrs", json.dumps(tev.attrs, sort_keys=True,
                                             default=str))
    group.set_tag(b"__source__", b"loongtrace")
    return group


# ---------------------------------------------------------------------------
# loongxprof: unified host/device Chrome-trace export
# ---------------------------------------------------------------------------

#: Chrome-trace process ids — one track group for the host spans, one for
#: the device dispatch legs
PID_HOST = 1
PID_DEVICE = 2

#: device legs get one tid each so Perfetto renders four stacked tracks
#: in pipeline order
_LEG_TIDS = {"h2d": 1, "submit": 2, "exec": 3, "d2h": 4}

#: args stripped by canonicalize(): run-dependent values (the tracer's
#: volatile attr set, plus the per-run dispatch id counter)
_CANON_VOLATILE = frozenset(_VOLATILE_ATTRS) | {"dispatch_id"}


def chrome_trace(tracer=None, timeline=None) -> dict:
    """The unified host/device execution timeline as a Chrome-trace JSON
    object (the ``traceEvents`` array format Perfetto loads directly).

    Host spans become complete ("ph":"X") events under pid ``PID_HOST``;
    device dispatch legs become complete events under pid ``PID_DEVICE``
    with one thread row per leg.  Both sides carry ``dispatch_id`` in
    their args where known, so a stalled ``device.roundtrip`` host span
    can be lined up with the exact H2D/submit/exec/D2H decomposition of
    the dispatch underneath it.  Defaults to the live planes
    (``trace.active_tracer()`` / ``xprof.active_timeline()``); either may
    be None — the export degrades to whichever side is recording."""
    if tracer is None:
        from . import active_tracer
        tracer = active_tracer()
    if timeline is None:
        from ..ops import xprof
        timeline = xprof.active_timeline()

    spans = tracer.finished_spans() if tracer is not None else []
    dispatches = timeline.dispatches() if timeline is not None else []

    # one shared perf_counter epoch: the device timeline's if it exists,
    # else the earliest host span (timestamps only need to be coherent
    # WITHIN the document)
    if timeline is not None:
        epoch = timeline.epoch
    elif spans:
        epoch = min(s._start_perf for s in spans)
    else:
        epoch = 0.0

    events: List[dict] = [
        {"ph": "M", "name": "process_name", "pid": PID_HOST,
         "args": {"name": "host (loongtrace spans)"}},
        {"ph": "M", "name": "process_name", "pid": PID_DEVICE,
         "args": {"name": "device (loongxprof dispatch legs)"}},
    ]
    for leg, tid in sorted(_LEG_TIDS.items(), key=lambda kv: kv[1]):
        events.append({"ph": "M", "name": "thread_name",
                       "pid": PID_DEVICE, "tid": tid,
                       "args": {"name": leg}})

    for span in spans:
        args = {k: v for k, v in span.attrs.items()}
        args["trace_id"] = span.trace_id
        args["status"] = span.status
        events.append({
            "ph": "X",
            "name": span.name,
            "cat": "host",
            "pid": PID_HOST,
            "tid": 1,
            "ts": round((span._start_perf - epoch) * 1e6, 3),
            "dur": round((span.duration_s or 0.0) * 1e6, 3),
            "args": args,
        })

    for rec in dispatches:
        for leg, t0, dur, attrs in rec.legs:
            args = {"dispatch_id": rec.id, "nbytes": rec.nbytes,
                    "program": rec.program or "unattributed",
                    "geometry": rec.geometry or "-"}
            args.update(attrs)
            events.append({
                "ph": "X",
                "name": leg,
                "cat": "device",
                "pid": PID_DEVICE,
                "tid": _LEG_TIDS.get(leg, 9),
                "ts": round(t0 * 1e6, 3),
                "dur": round(dur * 1e6, 3),
                "args": args,
            })

    return {"traceEvents": events, "displayTimeUnit": "ms"}


def canonicalize(doc: dict) -> bytes:
    """The Chrome-trace document reduced to its timing-independent
    structure, canonically serialized: timestamps/durations dropped,
    volatile args (dispatch ids, wall/thread) stripped, spans that are
    there by chance (``runtime.gc`` and its like) left out, entries sorted.
    Two runs of the same seeded storm yield identical bytes — the
    re-run-the-seed acceptance artifact, timeline edition."""
    entries: List[tuple] = []
    for ev in doc.get("traceEvents", []):
        if ev.get("cat") == "host" and ev.get("name") in VOLATILE_SPANS:
            continue    # a collection, a budget wait: present by chance
        args = tuple(sorted(
            (k, str(v)) for k, v in (ev.get("args") or {}).items()
            if k not in _CANON_VOLATILE))
        if ev.get("ph") == "M":
            entries.append(("meta", ev.get("name"), ev.get("pid"),
                            ev.get("tid", 0), args))
        else:
            entries.append(("slice", ev.get("cat"), ev.get("pid"),
                            ev.get("tid", 0), ev.get("name"), args))
    entries.sort(key=lambda e: json.dumps(e, default=str))
    return json.dumps(entries, sort_keys=True, separators=(",", ":"),
                      default=str).encode("utf-8")
