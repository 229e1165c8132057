"""loongtrace: the always-available, off-by-default pipeline span layer.

The paper's throughput headline (546 MB/s single-line, 68 MB/s regex
parse) says nothing about WHERE time goes once the parse hot path moves
onto the device plane; ParPaRaw-style parallel pipelines live or die on
per-stage latency balance.  This tracer makes the full event path — input
read → processor runner → device submit/resolve → batch/serialize →
flusher send — observable as spans, and makes the loongchaos plane's
injections, breaker transitions, spill/replay and retry decisions visible
as *span events* on one causal timeline.

Contract (mirrors chaos/plane.py, which established the idiom):

  * Disabled (the production default) every hook is ONE module-global
    read and an immediate return — `scripts/trace_overhead.py` gates the
    cost against a plain no-op call.
  * Enabled, sampling is deterministic per event-group key: the keep/drop
    draw depends only on ``(seed, key)`` (the seeded-stream idea from
    chaos/plan.py), so a traced soak replays the identical trace set.
  * The timeline's *structure* (names + attributes, never timestamps) is
    canonically serializable (`structure_bytes`), so two runs of the same
    seeded storm compare byte-identical.

Activation: programmatic ``enable()`` / scoped ``active()`` for tests, or
``LOONG_TRACE=1`` (with optional ``LOONG_TRACE_SAMPLE`` / ``LOONG_TRACE_SEED``)
via ``install_from_env()`` at application start.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import json
import os
import random
import threading
import time
from typing import Dict, List, Optional, Tuple

ENV_ENABLE = "LOONG_TRACE"
ENV_SAMPLE = "LOONG_TRACE_SAMPLE"
ENV_SEED = "LOONG_TRACE_SEED"

#: finished-span ring bound.  The ring keeps the NEWEST spans (a full
#: store used to refuse new ones, so /debug/timeline froze on the first
#: 50,000); an evicted span counts as dropped.  Sized to hold a whole
#: benchmark window that nobody drains (45 s at ~2,500 spans/s, twice
#: over) — about 120 MB when full, held only while tracing is on.
_SPAN_CAP = 1 << 18
_EVENT_CAP = 100_000    # timeline bound (matches chaos._SCHEDULE_CAP)
_MAX_EVENTS_PER_SPAN = 256


class Span:
    """One timed operation.  `end()` is idempotent; the tracer records the
    span at first end.  `add_event` attaches a named point event (kept in
    arrival order); events recorded after `end()` are dropped.

    Beside its wall seconds a span carries ``tid`` (the kernel's id of the
    thread that started it) and ``cpu_s``: the CPU seconds that thread
    spent between start and end — work, where ``duration_s`` is work and
    waiting.  ``cpu_s`` is None where no such reading exists: the span
    ended on another thread (two threads' CPU clocks do not subtract), its
    call site made it with ``cpu=False`` (a stopwatch that stays open
    while its thread does other groups' work), or whoever timed it took
    none (`close_at` without ``cpu_s``).  Both go into ``attrs`` when the span
    is recorded, so every exporter carries them."""

    __slots__ = ("tracer", "name", "trace_id", "span_id", "parent_id",
                 "start_wall", "_start_perf", "duration_s", "attrs",
                 "events", "status", "_ended", "tid", "_start_cpu", "cpu_s")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 span_id: int, parent_id: Optional[int],
                 attrs: Optional[dict] = None, cpu: bool = True):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start_wall = time.time()
        self._start_perf = time.perf_counter()
        self.duration_s: Optional[float] = None
        self.attrs = dict(attrs) if attrs else {}
        self.events: List[Tuple[str, float, dict]] = []
        self.status = "ok"
        self._ended = False
        self.tid = tracer.thread_id()
        self.cpu_s: Optional[float] = None
        # read last at the start and first at the end: the CPU interval
        # lies inside the wall interval, so cpu_s <= duration_s.  ``cpu``
        # False: no reading (a stopwatch, or a span whose caller timed it
        # and will `close_at` it) — the clock is a system call, and on a
        # sandboxed kernel a dear one
        self._start_cpu: Optional[float] = \
            time.thread_time() if cpu else None

    def set_attr(self, key: str, value) -> None:
        if not self._ended:
            self.attrs[key] = value

    def close_at(self, start_perf: float, duration_s: float,
                 store: bool = True, cpu_s: Optional[float] = None) -> None:
        """End with an interval somebody else measured (the device legs:
        one pair of perf_counter readings feeds this tracer and xprof) and
        the CPU seconds that caller read beside it on this thread, None
        where it read none."""
        if self._ended:
            return
        self._ended = True
        self.start_wall += start_perf - self._start_perf
        self._start_perf = start_perf
        self.duration_s = duration_s
        self.cpu_s = cpu_s
        self.tracer._record(self, store)

    def add_event(self, name: str, **attrs) -> None:
        if self._ended or len(self.events) >= _MAX_EVENTS_PER_SPAN:
            return
        self.events.append(
            (name, time.perf_counter() - self._start_perf, attrs))

    def end(self, status: Optional[str] = None) -> None:
        if self._ended:
            return
        self._ended = True
        if status is not None:
            self.status = status
        start_cpu = self._start_cpu
        if start_cpu is not None and self.tracer.thread_id() == self.tid:
            self.cpu_s = time.thread_time() - start_cpu
        self.duration_s = time.perf_counter() - self._start_perf
        self.tracer._record(self)

    # context-manager sugar: ``with trace.span("x") as sp: ...``
    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end("error" if exc_type is not None else None)


class TraceEvent:
    """A free-standing timeline entry (breaker transition, chaos
    injection, spill...) — recorded even when no span is current, so the
    causal storm timeline survives thread hops."""

    __slots__ = ("name", "seq", "wall", "attrs", "span_id")

    def __init__(self, name: str, seq: int, attrs: dict,
                 span_id: Optional[int]):
        self.name = name
        self.seq = seq
        self.wall = time.time()
        self.attrs = attrs
        self.span_id = span_id

    def structure_key(self) -> tuple:
        """Identity stripped of everything timing- and thread-dependent."""
        return (self.name,
                tuple(sorted((k, _stable(v)) for k, v in self.attrs.items())))


def _stable(v):
    """Canonicalize an attribute value for structure comparison: floats
    are rounded (chaos Decision.key idiom) so re-derived magnitudes
    compare equal; everything else must already be primitive."""
    if isinstance(v, float):
        return round(v, 9)
    return v


class TraceConfig:
    __slots__ = ("sample_rate", "seed")

    def __init__(self, sample_rate: float = 1.0, seed: int = 0):
        self.sample_rate = float(sample_rate)
        self.seed = int(seed)


class Tracer:
    """Process-wide span/timeline store.  All mutation is lock-cheap:
    one lock, short critical sections, bounded buffers."""

    def __init__(self, config: Optional[TraceConfig] = None):
        self.config = config or TraceConfig()
        self._lock = threading.Lock()
        # finished spans, arrival order; the newest _SPAN_CAP of them
        self._spans: collections.deque = collections.deque(maxlen=_SPAN_CAP)
        self._timeline: List[TraceEvent] = []
        self._event_seq = itertools.count()
        self._span_ids = itertools.count(1)
        self._dropped_spans = 0
        self._sample_cache: Dict[str, bool] = {}
        self._group_seq: Dict[str, int] = {}
        self._tls = threading.local()
        # every finished span's duration, by name, in the normal metrics
        # tree (loong_span_seconds{name=...}): rare spans are counted over
        # the whole run, whatever the span ring still holds
        self._span_hists: Dict[str, object] = {}
        # collections the gc hook saw, not yet folded in: the hook runs at
        # any bytecode boundary, possibly under this tracer's own lock, so
        # it only appends here (no lock, no span) — see note_gc
        self._gc_pending: List[tuple] = []
        self._gc_fold_lock = threading.Lock()

    # -- sampling (deterministic per key) -----------------------------------

    def should_sample(self, key: str) -> bool:
        """Keep/drop draw for one event-group key.  Depends only on
        (seed, key) — the chaos/plan.py seeded-stream idea — so replaying
        the same workload traces the identical group set."""
        rate = self.config.sample_rate
        if rate >= 1.0:
            return True
        if rate <= 0.0:
            return False
        with self._lock:
            hit = self._sample_cache.get(key)
            if hit is None:
                hit = (random.Random(f"{self.config.seed}:{key}").random()
                       < rate)
                if len(self._sample_cache) < _EVENT_CAP:
                    self._sample_cache[key] = hit
        return hit

    def next_group_key(self, stream: str) -> str:
        """Stable per-stream sequence key: the Nth group of stream S gets
        key "S:N" in every run that feeds S the same groups in order."""
        with self._lock:
            n = self._group_seq.get(stream, 0)
            self._group_seq[stream] = n + 1
        return f"{stream}:{n}"

    # -- spans --------------------------------------------------------------

    def thread_id(self) -> int:
        """The calling thread's kernel id (``threading.get_native_id()``,
        a system call, asked once a thread): what `/proc/self/task` and
        /debug/status ``threads`` know it by."""
        tls = self._tls
        try:
            return tls.tid
        except AttributeError:
            tls.tid = threading.get_native_id()
            return tls.tid

    def start_span(self, name: str, trace_id: str = "",
                   parent: Optional[Span] = None,
                   attrs: Optional[dict] = None, cpu: bool = True) -> Span:
        if parent is None:
            parent = self.current_span()
        if parent is not None and not trace_id:
            trace_id = parent.trace_id
        return Span(self, name, trace_id, next(self._span_ids),
                    parent.span_id if parent is not None else None, attrs,
                    cpu)

    def child_or_sampled(self, stream: str, name: str,
                         attrs: Optional[dict] = None,
                         cpu: bool = True) -> Optional[Span]:
        """Span-creation policy for instrumented stages: under a live
        (already-sampled) root span the stage always records as its
        child; a rootless stage draws its own deterministic keep/drop
        from the per-stream key sequence — so total span volume scales
        with the sample rate at EVERY instrumentation point, not just
        the pipeline root."""
        parent = self.current_span()
        if parent is not None:
            return self.start_span(name, parent=parent, attrs=attrs, cpu=cpu)
        if self.config.sample_rate >= 1.0:       # fast path: no key draw
            return self.start_span(name, attrs=attrs, cpu=cpu)
        key = self.next_group_key(stream)
        if not self.should_sample(key):
            return None
        return self.start_span(name, trace_id=key, attrs=attrs, cpu=cpu)

    def start_stage(self, stream: str, name: str,
                    attrs: Optional[dict] = None) -> Optional[Span]:
        """`child_or_sampled`, made current on this thread: what the stage
        calls synchronously nests under it, so its self time is its own.
        `end()` pops it — call that from the stage's ``finally``."""
        sp = self.child_or_sampled(stream, name, attrs)
        if sp is not None:
            self.push_current(sp)
        return sp

    def record_timed(self, stream: str, name: str, start_perf: float,
                     duration_s: float, attrs: Optional[dict] = None,
                     cpu_s: Optional[float] = None) -> None:
        """A finished span from an interval already measured, under the
        `child_or_sampled` policy: child of the current span, else drawn
        from ``stream``'s key sequence.  ``cpu_s``: the pair of
        ``time.thread_time()`` readings the caller took beside its pair of
        ``perf_counter`` readings, on this thread."""
        sp = self.child_or_sampled(stream, name, attrs, cpu=False)
        if sp is not None:
            sp.close_at(start_perf, duration_s, cpu_s=cpu_s)

    def span_histogram(self, name: str):
        h = self._span_hists.get(name)
        if h is None:
            from ..monitor.metrics import shared_histogram
            h = self._span_hists[name] = shared_histogram(
                "span_seconds", category="trace", labels={"name": name})
        return h

    def note_gc(self, start_perf: float, duration_s: float,
                generation: int, cpu_s: Optional[float] = None) -> None:
        """The gc hook's whole work: one lock-free append.  The next
        recorded span folds the list (the periodic spans see to it that
        one comes within the minute on an idle agent).  The collector ran
        on the thread that tripped it: ``cpu_s`` and the thread id are
        that thread's, whoever folds the list."""
        self._gc_pending.append((start_perf, duration_s, generation,
                                 self.current_span(), cpu_s,
                                 self.thread_id()))

    def _fold_gc(self) -> None:
        """Pending collections into `runtime.gc` spans: the histogram takes
        every one, the span ring only generation >= 1 or >= 1 ms."""
        if not self._gc_fold_lock.acquire(blocking=False):
            return                      # another thread is folding them
        try:
            pending, self._gc_pending = self._gc_pending, []
            for start, dur, gen, parent, cpu_s, tid in pending:
                sp = Span(self, "runtime.gc",
                          parent.trace_id if parent is not None else "",
                          next(self._span_ids),
                          parent.span_id if parent is not None else None,
                          {"generation": gen}, cpu=False)
                sp.tid = tid
                sp.close_at(start, dur, store=gen >= 1 or dur >= 1e-3,
                            cpu_s=cpu_s)
        finally:
            self._gc_fold_lock.release()

    def _record(self, span: Span, store: bool = True) -> None:
        if self._gc_pending and span.name != "runtime.gc":
            self._fold_gc()
        # in attrs, where every exporter (and the benchmark's launcher)
        # already looks; both are volatile, so the structure stays the seed's
        span.attrs["cpu_s"] = span.cpu_s
        span.attrs["tid"] = span.tid
        self.span_histogram(span.name).observe(span.duration_s or 0.0)
        if store:
            with self._lock:
                if len(self._spans) == _SPAN_CAP:
                    self._dropped_spans += 1
                self._spans.append(span)
        stack = getattr(self._tls, "stack", None)
        if stack and span in stack:
            stack.remove(span)

    # current-span stack (per thread) — push/pop is explicit so the
    # overlapped dispatch loop can detach group N's span while N+1 packs
    def push_current(self, span: Span) -> None:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append(span)

    def pop_current(self, span: Optional[Span] = None) -> None:
        stack = getattr(self._tls, "stack", None)
        if not stack:
            return
        if span is None:
            stack.pop()
        elif span in stack:
            stack.remove(span)

    def current_span(self) -> Optional[Span]:
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def root_span(self) -> Optional[Span]:
        """The nearest parentless span under the current one on this
        thread (the group's ``pipeline.process`` while a stage runs): the
        parent for work that outlives the stage that starts it.  None when
        the current span is itself the root, or nothing is current."""
        stack = getattr(self._tls, "stack", None)
        if stack:
            for sp in reversed(stack):
                if sp.parent_id is None:
                    return sp if sp is not stack[-1] else None
        return None

    # -- timeline -----------------------------------------------------------

    def event(self, name: str, **attrs) -> None:
        cur = self.current_span()
        if cur is not None:
            cur.add_event(name, **attrs)
        ev = TraceEvent(name, next(self._event_seq), attrs,
                        cur.span_id if cur is not None else None)
        with self._lock:
            if len(self._timeline) < _EVENT_CAP:
                self._timeline.append(ev)

    # -- retrieval ----------------------------------------------------------

    def finished_spans(self) -> List[Span]:
        if self._gc_pending:
            self._fold_gc()
        with self._lock:
            return list(self._spans)

    def timeline(self) -> List[TraceEvent]:
        with self._lock:
            return list(self._timeline)

    def timeline_by_name(self) -> Dict[str, List[TraceEvent]]:
        out: Dict[str, List[TraceEvent]] = {}
        for ev in self.timeline():
            out.setdefault(ev.name, []).append(ev)
        return out

    def drain(self) -> Tuple[List[Span], List[TraceEvent]]:
        """Remove-and-return everything recorded so far (self-monitor
        export cadence): each span/event ships exactly once."""
        if self._gc_pending:
            self._fold_gc()
        with self._lock:
            spans = list(self._spans)
            self._spans.clear()
            events, self._timeline = self._timeline, []
        return spans, events

    def structure(self) -> List[tuple]:
        """The timeline + span set reduced to its timing-independent
        structure, canonically ordered: per-name event subsequences keep
        arrival order (deterministic under one thread, and per-point
        deterministic like the chaos schedule under many), names sort
        lexically, spans reduce to (name, status, sorted attr keys,
        event names)."""
        events = self.timeline_by_name()
        out: List[tuple] = []
        for name in sorted(events):
            for ev in events[name]:
                out.append(("event",) + ev.structure_key())
        spans = sorted(
            ((s.name, s.status,
              tuple(sorted((k, _stable(v)) for k, v in s.attrs.items()
                           if k not in _VOLATILE_ATTRS)),
              tuple(e[0] for e in s.events))
             for s in self.finished_spans()
             if s.name not in VOLATILE_SPANS))
        out.extend(("span",) + s for s in spans)
        return out

    def structure_bytes(self) -> bytes:
        """Byte-comparable canonical serialization of `structure()` — the
        re-run-the-seed acceptance artifact."""
        return json.dumps(self.structure(), sort_keys=True,
                          separators=(",", ":"),
                          default=str).encode("utf-8")

    def stats(self) -> dict:
        if self._gc_pending:
            self._fold_gc()
        with self._lock:
            return {"spans": len(self._spans),
                    "events": len(self._timeline),
                    "dropped_spans": self._dropped_spans,
                    "cpu_clock": _cpu_clock}


#: span attributes whose values are run-dependent (sizes are stable, ids
#: and timings are not) — excluded from structure comparison.
#: dispatch_id is loongxprof's per-run correlation counter: interleaving
#: under concurrency may renumber dispatches between identical runs;
#: cpu_s and tid are every span's CPU seconds and kernel thread id
_VOLATILE_ATTRS = frozenset({"duration_ms", "wall", "thread",
                             "dispatch_id", "cpu_s", "tid"})


#: spans whose very presence is run-dependent (a collection, a wait on
#: the budget, work on a wall-clock cadence) — excluded the same way
VOLATILE_SPANS = frozenset({"runtime.gc", "device.acquire",
                             "checkpoint.dump", "ledger.audit",
                             "self_monitor.tick"})


# ---------------------------------------------------------------------------
# module-level plane (the chaos/plane.py shape): one global, one branch


_tracer: Optional[Tracer] = None


def is_active() -> bool:
    return _tracer is not None


def active_tracer() -> Optional[Tracer]:
    """THE disabled-path hook: call sites read this once; None means
    tracing is off and nothing else may run."""
    return _tracer


def enable(config: Optional[TraceConfig] = None) -> Tracer:
    global _tracer
    t = Tracer(config)
    cpu_clock()
    _tracer = t
    if _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)
    return t


def disable() -> None:
    global _tracer
    _tracer = None
    if _gc_hook in gc.callbacks:
        gc.callbacks.remove(_gc_hook)


_cpu_clock: Optional[dict] = None


def cpu_clock() -> dict:
    """What a reading of this kernel's thread CPU clock costs and how fine
    it steps, probed once a process (at the first `enable()`):
    ``cost_us`` (one ``time.thread_time()``, the least of eight) and
    ``step_us`` (the clock's least step seen while spinning, at most 30
    ms).  A Linux kernel reads some 0.3 µs in 1 µs steps; a sandboxed one
    (the chip hosts') 7–15 µs in 10 ms steps — there a span's ``cpu_s`` is
    a count of 10 ms ticks, good in sums over a slice and void alone, and
    tracing on costs that reading twice a span.  In /debug/status
    ``trace``, so that whoever reads a CPU column knows its grain."""
    global _cpu_clock
    if _cpu_clock is None:
        cost = min(_timed(time.thread_time) for _ in range(8))
        t_end = time.perf_counter() + 0.03
        last, step = time.thread_time(), None
        while step is None and time.perf_counter() < t_end:
            now = time.thread_time()
            if now != last:
                step = now - last
        _cpu_clock = {"cost_us": round(cost * 1e6, 3),
                      "step_us": None if step is None
                      else round(step * 1e6, 3)}
    return _cpu_clock


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


_gc_t0 = 0.0
_gc_cpu0 = 0.0


def _gc_hook(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry, installed only while a tracer is active:
    times every collection (they never nest) for `runtime.gc`, on the wall
    and on the CPU clock of the thread that tripped it."""
    global _gc_t0, _gc_cpu0
    if phase == "start":
        _gc_t0 = time.perf_counter()
        _gc_cpu0 = time.thread_time()
    elif _gc_t0:
        t = _tracer
        if t is not None:
            cpu_s = time.thread_time() - _gc_cpu0
            t.note_gc(_gc_t0, time.perf_counter() - _gc_t0,
                      info.get("generation", -1), cpu_s)
        _gc_t0 = 0.0


@contextlib.contextmanager
def active(config: Optional[TraceConfig] = None):
    """Scoped activation for tests: ``with trace.active() as t: ...``."""
    t = enable(config)
    try:
        yield t
    finally:
        disable()


def install_from_env(env=os.environ) -> bool:
    """LOONG_TRACE=1 activates tracing at application start;
    LOONG_TRACE_SAMPLE (float, default 1.0) and LOONG_TRACE_SEED (int,
    default 0) shape deterministic sampling."""
    raw = env.get(ENV_ENABLE)
    if not raw or raw.strip().lower() in ("0", "false", "no", "off"):
        return False
    try:
        rate = float(env.get(ENV_SAMPLE, "1.0"))
    except ValueError:
        rate = 1.0
    try:
        seed = int(env.get(ENV_SEED, "0"))
    except ValueError:
        seed = 0
    enable(TraceConfig(sample_rate=rate, seed=seed))
    return True


# -- hot-path hooks: each is one global read + branch when disabled ---------


def event(name: str, **attrs) -> None:
    """Record a timeline event (and attach to the current span, if any).
    Disabled: a single branch."""
    t = _tracer
    if t is None:
        return
    t.event(name, **attrs)


def start_span(name: str, trace_id: str = "",
               parent: Optional[Span] = None,
               attrs: Optional[dict] = None) -> Optional[Span]:
    t = _tracer
    if t is None:
        return None
    return t.start_span(name, trace_id, parent, attrs)


def span(name: str, **attrs):
    """``with trace.span("stage"): ...`` — returns a no-op context when
    disabled (the with-statement itself is the only residual cost, so
    hot paths should prefer an ``is_active()`` guard)."""
    t = _tracer
    if t is None:
        return contextlib.nullcontext()
    sp = t.start_span(name, attrs=attrs or None)
    return sp


def current_span() -> Optional[Span]:
    t = _tracer
    if t is None:
        return None
    return t.current_span()


def status() -> Optional[dict]:
    """The /debug/status ``trace`` section; None while tracing is off."""
    t = _tracer
    if t is None:
        return None
    return t.stats()
