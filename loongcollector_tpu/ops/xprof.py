"""loongxprof: the device-execution timeline plane (off by default).

The four observability planes before this one (loongtrace / loongprof /
loongledger / loongslo) stop at the host: ``device.roundtrip`` is one
opaque stopwatch span.  This plane decomposes every device dispatch into
its legs —

  * ``h2d``      — host pack into the leased batch-ring slot (the H2D
    staging work; for the sharded plane, the per-shard device_put);
  * ``submit``   — the async kernel dispatch call itself;
  * ``exec``     — dispatch return → first output ready (the device
    execution window the host observes);
  * ``d2h``      — materialisation of the outputs into host numpy;

correlated by a **dispatch id** minted at `DevicePlane.submit` and
threaded through `DeviceFuture`, so the Chrome-trace exporter
(trace/export.py) can line device legs up under the host spans that
caused them.

Contract (mirrors chaos/plane.py and trace/tracer.py, which established
the idiom):

  * Disabled (the production default) every hook is ONE module-global
    read and an immediate return — `scripts/xprof_overhead.py` gates the
    cost against a plain no-op baseline (≤5% paired-min, like the
    trace/prof/ledger/slo gates).
  * Enabled, per-(program, geometry, leg) segment histograms feed the
    normal metrics tree (``device_segment_seconds``), so the dispatch
    decomposition is scrapable from /metrics without pulling the full
    timeline.
  * The timeline's *structure* (programs, geometries, leg names — never
    timestamps) is canonically serializable through
    ``trace.export.canonicalize``, so two runs of the same seeded storm
    compare byte-identical like the tracer does.

Activation: programmatic ``enable()`` / scoped ``active()`` for tests,
or ``LOONG_XPROF=1`` via ``install_from_env()`` at application start.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from .. import trace

ENV_ENABLE = "LOONG_XPROF"

_DISPATCH_CAP = 50_000        # bounded like the tracer's span ring
_MAX_LEGS_PER_DISPATCH = 16   # submit/h2d/exec/d2h plus retries/annexes

#: the decomposition legs in pipeline order (export + bench ordering)
LEGS = ("h2d", "submit", "exec", "d2h")


class DispatchRecord:
    """One device dispatch's decomposition: identity, program, geometry,
    and the timed legs (start offsets are relative to the timeline
    epoch — perf_counter based, the same clock the tracer's spans use)."""

    __slots__ = ("id", "nbytes", "program", "geometry", "legs", "closed")

    def __init__(self, xid: int, nbytes: int):
        self.id = xid
        self.nbytes = nbytes
        self.program: Optional[str] = None
        self.geometry: Optional[str] = None
        # [(leg, start_s_rel_epoch, dur_s, attrs)]
        self.legs: List[Tuple[str, float, float, dict]] = []
        self.closed = False

    def leg_durations(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for leg, _t0, dur, _a in self.legs:
            out[leg] = out.get(leg, 0.0) + dur
        return out


class DeviceTimeline:
    """Process-wide dispatch-decomposition store.  All mutation is
    lock-cheap: one lock, short critical sections, bounded buffers."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._records: Dict[int, DispatchRecord] = {}
        self._order: List[int] = []
        self._ids = itertools.count(1)
        self._dropped = 0
        self._closed_total = 0
        #: perf_counter epoch — every leg start is stored relative to this
        self.epoch = time.perf_counter()

    # -- recording ----------------------------------------------------------

    def begin(self, nbytes: int) -> int:
        xid = next(self._ids)
        rec = DispatchRecord(xid, nbytes)
        with self._lock:
            if len(self._order) < _DISPATCH_CAP:
                self._records[xid] = rec
                self._order.append(xid)
            else:
                self._dropped += 1
        return xid

    def annotate(self, xid: int, program: Optional[str] = None,
                 geometry: Optional[str] = None) -> None:
        with self._lock:
            rec = self._records.get(xid)
            if rec is None:
                return
            if program is not None:
                rec.program = program
            if geometry is not None:
                rec.geometry = geometry

    def leg(self, xid: int, name: str, t_start: float, dur_s: float,
            **attrs) -> None:
        """Record one timed leg.  ``t_start`` is an absolute
        perf_counter() reading; it is stored relative to the epoch."""
        with self._lock:
            rec = self._records.get(xid)
            if rec is None or len(rec.legs) >= _MAX_LEGS_PER_DISPATCH:
                return
            rec.legs.append((name, t_start - self.epoch, dur_s, attrs))

    def close(self, xid: int) -> None:
        """Dispatch settled (materialised): fold its legs into the
        per-(program, geometry, leg) decomposition histograms.  Program
        and geometry are known by now — the dispatching caller annotates
        between submit and materialise."""
        with self._lock:
            rec = self._records.get(xid)
            if rec is None or rec.closed:
                return
            rec.closed = True
            self._closed_total += 1
            legs = list(rec.legs)
            program = rec.program or "unattributed"
            geometry = rec.geometry or "-"
        for leg, _t0, dur, _a in legs:
            _segment_histogram(program, geometry, leg).observe(dur)

    # -- retrieval ----------------------------------------------------------

    def dispatches(self) -> List[DispatchRecord]:
        with self._lock:
            return [self._records[x] for x in self._order]

    def decomposition(self) -> Dict[str, dict]:
        """Per-(program, geometry) leg totals — the compact /debug and
        bench view (full distributions live in the metric histograms)."""
        out: Dict[str, dict] = {}
        for rec in self.dispatches():
            key = f"{rec.program or 'unattributed'}:{rec.geometry or '-'}"
            row = out.setdefault(key, {
                "dispatches": 0, "closed": 0, "nbytes": 0,
                "legs_ms": {}, "legs_count": {}})
            row["dispatches"] += 1
            row["closed"] += 1 if rec.closed else 0
            row["nbytes"] += rec.nbytes
            for leg, dur in rec.leg_durations().items():
                row["legs_ms"][leg] = round(
                    row["legs_ms"].get(leg, 0.0) + dur * 1000.0, 3)
                row["legs_count"][leg] = row["legs_count"].get(leg, 0) + 1
        return out

    def stats(self) -> dict:
        with self._lock:
            return {"dispatches": len(self._order),
                    "closed": self._closed_total,
                    "dropped": self._dropped}


# ---------------------------------------------------------------------------
# decomposition histograms: one shared instrument per (program, geometry,
# leg) — bounded by the batch/length bucketing upstream


def _segment_histogram(program: str, geometry: str, leg: str):
    from ..monitor.metrics import shared_histogram
    return shared_histogram("device_segment_seconds",
                            labels={"component": "xprof",
                                    "program": program,
                                    "geometry": geometry,
                                    "leg": leg})


# ---------------------------------------------------------------------------
# module-level plane (the chaos/plane.py shape): one global, one branch


_timeline: Optional[DeviceTimeline] = None

_tls = threading.local()


def is_active() -> bool:
    return _timeline is not None


def active_timeline() -> Optional[DeviceTimeline]:
    """THE disabled-path hook: call sites read this once; None means the
    plane is off and nothing else may run."""
    return _timeline


def enable() -> DeviceTimeline:
    global _timeline
    t = DeviceTimeline()
    _timeline = t
    return t


def disable() -> None:
    global _timeline
    _timeline = None


@contextlib.contextmanager
def active():
    """Scoped activation for tests: ``with xprof.active() as t: ...``."""
    t = enable()
    try:
        yield t
    finally:
        disable()


def install_from_env(env=os.environ) -> bool:
    """LOONG_XPROF=1 activates the device timeline at application
    start."""
    raw = env.get(ENV_ENABLE)
    if not raw or raw.strip().lower() in ("0", "false", "no", "off"):
        return False
    enable()
    return True


# -- hot-path hooks: each is one global read + branch when disabled ---------


def begin_dispatch(nbytes: int) -> int:
    """Mint a dispatch id (DevicePlane.submit).  Disabled: a single
    branch, returns 0 (the null id every other hook short-circuits on)."""
    t = _timeline
    if t is None:
        return 0
    return t.begin(nbytes)


def leg(xid: int, name: str, t_start: float, dur_s: float, **attrs) -> None:
    """Record one timed leg for dispatch ``xid``.  Disabled (or null id):
    a single branch."""
    t = _timeline
    if t is None or not xid:
        return
    t.leg(xid, name, t_start, dur_s, **attrs)


def annotate(xid: int, program: Optional[str] = None,
             geometry: Optional[str] = None) -> None:
    t = _timeline
    if t is None or not xid:
        return
    t.annotate(xid, program=program, geometry=geometry)


def close_dispatch(xid: int) -> None:
    t = _timeline
    if t is None or not xid:
        return
    t.close(xid)


def leg_attrs(nbytes: int, xid: int) -> dict:
    """What every device-leg span of the tracer carries: the dispatch's
    bytes and, where this plane is on, the id that lines the span up with
    the timeline's leg."""
    return {"nbytes": nbytes, "dispatch_id": xid} if xid \
        else {"nbytes": nbytes}


def note_dispatch(fut, program: str, geometry: str,
                  pack_t0: Optional[float] = None,
                  pack_dur: Optional[float] = None,
                  pack_cpu: Optional[float] = None) -> None:
    """One-call convenience for the dispatch window
    (device_stream.DeviceStream.submit): attribute the future's dispatch to a
    program + geometry and attach the pack/H2D leg the caller timed —
    to the timeline as ``h2d`` and, the same reading, to the tracer as a
    ``device.pack`` span under the stage that dispatched (``pack_cpu``:
    the packing thread's CPU seconds over the same interval).  Both planes
    off: the pack was not timed, two branches."""
    t = _timeline
    if pack_dur is None and t is None:
        return
    xid = getattr(fut, "dispatch_id", 0)
    if pack_dur is not None and pack_t0 is not None:
        tracer = trace.active_tracer()
        if tracer is not None:
            tracer.record_timed("device", "device.pack", pack_t0, pack_dur,
                                leg_attrs(getattr(fut, "_nbytes", 0), xid),
                                pack_cpu)
    if t is None or not xid:
        return
    t.annotate(xid, program=program, geometry=geometry)
    if pack_dur is not None and pack_t0 is not None:
        t.leg(xid, "h2d", pack_t0, pack_dur)


# -- current-dispatch TLS: lets code running INSIDE the submitted kernel
#    (ShardedKernel._dispatch runs under plane.submit's kernel call)
#    attach legs to the enclosing dispatch --------------------------------


def set_current_dispatch(xid: int) -> None:
    _tls.xid = xid


def current_dispatch() -> int:
    """The dispatch id of the enclosing plane.submit, 0 outside one.
    Disabled: a single branch."""
    t = _timeline
    if t is None:
        return 0
    return getattr(_tls, "xid", 0)


# -- status ----------------------------------------------------------------


def status() -> Optional[dict]:
    """The /debug/status ``xprof`` section; None while the plane is
    off (section absent, matching the other gated planes)."""
    t = _timeline
    if t is None:
        return None
    doc = t.stats()
    doc["decomposition"] = t.decomposition()
    return doc
