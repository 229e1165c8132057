"""Async overlapped host↔device data plane.

The reference overlaps every pipeline stage with dedicated threads and queue
hops (core/runner/ProcessorRunner.cpp:90-189, core/runner/FlusherRunner.cpp:168);
its BoundedProcessQueue watermarks gate the producers
(core/collection_pipeline/queue/BoundedProcessQueue.cpp:89-93).  The TPU
analogue (SURVEY.md §7 step 4, §5.8) is this plane: device kernel dispatches
are ASYNC (jax returns device buffers immediately; computation proceeds in the
background), so the host packs and dispatches chunk N+1 while the device
executes chunk N, and materialises results strictly as needed.  The copy
back of a dispatch's outputs is started by `submit` itself, behind the program
on the runtime's own threads, so materialisation finds them on the host.

Back-pressure contract: every dispatch acquires from a process-wide in-flight
byte budget and releases it on materialisation.  When the device stalls, the
budget fills, `submit` blocks, the runner thread stops popping, the bounded process queues hit their high watermark, and the file
inputs get feedback-blocked — the exact chain the reference builds between
FlusherRunner, the sender queues and the process queues, extended one hop
further onto the device.

Nothing here imports jax: the plane is agnostic to WHAT is dispatched — it
only requires that calling the kernel is cheap (async dispatch) and that
`numpy.asarray` on the returned buffers blocks until the device is done.
That contract holds for jax on every backend and for the latency-injection
test kernel below.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from .. import chaos, prof, trace
from . import xprof
from ..utils.logger import get_logger

log = get_logger("device_plane")

_DEFAULT_BUDGET = 64 * 1024 * 1024  # bytes of packed rows in flight

FP_SUBMIT = chaos.register_point("device_plane.submit")

_tls = threading.local()

# ---------------------------------------------------------------------------
# loongtenant: per-tenant (per-pipeline) shares of the in-flight byte budget.
#
# The chip-lane share mechanics (ops/chip_lanes.ChipLane.over_share),
# re-keyed per pipeline: with N registered tenants each gets budget/N, and
# a tenant dispatching past its share must drain ITS OWN oldest in-flight
# chunk first (the caller's on_wait hook — the same never-sleep-owning-
# budget discipline, per tenant).  Other tenants are untouched: they only
# ever wait on the GLOBAL budget, so one hot pipeline's backlog drains
# through its own lane instead of starving the other 255.
#
# The registry is module-level (not per-plane) so reset_for_testing()
# cannot orphan accounting, and the worker binds its current tenant via
# TLS (set_thread_tenant) exactly like chip_lanes.set_thread_lane.

_tenant_lock = threading.Lock()
_tenant_registered: set = set()            # tenant names holding a share
_tenant_inflight: Dict[str, int] = {}      # name -> dispatched bytes in flight


def set_thread_tenant(name: Optional[str]) -> None:
    """Bind THIS thread's dispatches to a tenant (the processor runner
    sets the owning pipeline's name around process/complete; None
    unbinds)."""
    _tls.tenant = name


def current_tenant() -> Optional[str]:
    return getattr(_tls, "tenant", None)


def register_tenant(name: str) -> None:
    """Grant `name` a share of the plane budget (pipeline manager, at
    config apply).  Re-registering an existing tenant (a reload's next
    generation) is a no-op — the share follows the NAME, not the
    generation."""
    if not name:
        return
    with _tenant_lock:
        _tenant_registered.add(name)


def unregister_tenant(name: str) -> None:
    """Drop `name`'s share (pipeline removed).  In-flight accounting for
    still-unresolved futures survives until they settle."""
    with _tenant_lock:
        _tenant_registered.discard(name)
        if not _tenant_inflight.get(name):
            _tenant_inflight.pop(name, None)


def tenant_count() -> int:
    with _tenant_lock:
        return len(_tenant_registered)


def _tenant_note(name: str, delta: int) -> None:
    with _tenant_lock:
        cur = max(0, _tenant_inflight.get(name, 0) + delta)
        if cur == 0 and name not in _tenant_registered:
            _tenant_inflight.pop(name, None)
        else:
            _tenant_inflight[name] = cur


def tenant_inflight_bytes(name: str) -> int:
    with _tenant_lock:
        return _tenant_inflight.get(name, 0)


def tenant_share_bytes(budget_bytes: int) -> int:
    """One tenant's slice of the plane budget (0 = sharing inactive:
    fewer than two tenants, or an unbounded plane)."""
    with _tenant_lock:
        n = len(_tenant_registered)
    if n <= 1 or not budget_bytes:
        return 0
    return budget_bytes // n


def tenant_over_share(name: str, nbytes: int, budget_bytes: int) -> bool:
    """True when dispatching `nbytes` more would push `name` past its
    per-tenant share.  Never true with <2 tenants (the single-tenant
    agent keeps the whole budget — exactly the pre-tenant behaviour)."""
    share = tenant_share_bytes(budget_bytes)
    if not share:
        return False
    with _tenant_lock:
        held = _tenant_inflight.get(name, 0)
    return held > 0 and held + nbytes > share


def tenant_snapshot(budget_bytes: Optional[int] = None) -> Dict[str, dict]:
    """Per-tenant budget view for /debug/status (observe-only)."""
    if budget_bytes is None:
        plane = DevicePlane._instance
        budget_bytes = plane.budget_bytes if plane is not None else 0
    share = tenant_share_bytes(budget_bytes)
    with _tenant_lock:
        names = set(_tenant_registered) | set(_tenant_inflight)
        rows = {n: _tenant_inflight.get(n, 0) for n in names}
    return {n: {"inflight_bytes": held,
                "share_bytes": share,
                "over_share": bool(share and held > share)}
            for n, held in sorted(rows.items())}


def reset_tenants_for_testing() -> None:
    with _tenant_lock:
        _tenant_registered.clear()
        _tenant_inflight.clear()

# ---------------------------------------------------------------------------
# loongxprof: device-memory accounting — a ledger-style live/peak byte
# ledger per allocation family.  Always on (unlike the timeline): the
# hooks fire at lease/dispatch rate, not per-event rate, and every prior
# device PR has needed exactly this number after the fact.  Families:
#
#   ring_slots       — leased batch-ring staging slots (device_stream)
#   resident_columns — HBM-resident inter-stage columns held by in-flight
#                      fused dispatches (fused_pipeline)
#   dfa_tables       — memoized FusedDFA constant tables (regex/fuse)
#   sharded_staging  — per-shard device_put staging (parallel/mesh)
#   side_arenas      — kernel-side staging pools (segment_reduce etc.)
#
# Conservation contract: at quiesce, ``ring_slots`` live bytes must equal
# the ring's leased bytes (both zero once every slot returned) — the
# auditor folds the residual into its quiesced snapshot check.

MEM_FAMILIES = ("ring_slots", "resident_columns", "dfa_tables",
                "sharded_staging", "side_arenas")

_mem_lock = threading.Lock()
_mem: Dict[str, List[int]] = {}   # family -> [live, peak, allocs, frees]


def mem_note_alloc(family: str, nbytes: int) -> None:
    """Charge `nbytes` of device-resident memory to `family`."""
    if nbytes <= 0:
        return
    with _mem_lock:
        row = _mem.get(family)
        if row is None:
            row = _mem[family] = [0, 0, 0, 0]
        row[0] += nbytes
        if row[0] > row[1]:
            row[1] = row[0]
        row[2] += 1


def mem_note_free(family: str, nbytes: int) -> None:
    """Credit `nbytes` back to `family`.  Live bytes clamp at zero: a
    double-free is an accounting bug upstream, never a negative gauge."""
    if nbytes <= 0:
        return
    with _mem_lock:
        row = _mem.get(family)
        if row is None:
            row = _mem[family] = [0, 0, 0, 0]
        row[0] = max(0, row[0] - nbytes)
        row[3] += 1


def mem_live_bytes(family: str) -> int:
    with _mem_lock:
        row = _mem.get(family)
        return row[0] if row is not None else 0


def device_memory_status() -> dict:
    """Per-family live/peak ledger — the /debug/status ``device_memory``
    section and the auditor's conservation input."""
    with _mem_lock:
        fams = {f: {"live_bytes": row[0], "peak_bytes": row[1],
                    "allocs": row[2], "frees": row[3]}
                for f, row in sorted(_mem.items())}
        total_live = sum(row[0] for row in _mem.values())
    return {"families": fams, "total_live_bytes": total_live}


def mem_reset_for_testing() -> None:
    with _mem_lock:
        _mem.clear()

# submit→resolve stopwatch sink: one shared histogram (lazy so importing
# the plane never touches the metrics registry)
_rtt_hist = None


def roundtrip_histogram():
    """The device round-trip latency histogram (dispatch → materialise),
    observed by every DeviceFuture that resolves successfully."""
    global _rtt_hist
    if _rtt_hist is None:
        from ..monitor.metrics import shared_histogram
        _rtt_hist = shared_histogram("device_roundtrip_seconds",
                                     labels={"component": "device_plane"})
    return _rtt_hist


_dispatch_counter = None
_dispatch_counter_lock = threading.Lock()


def dispatch_counter():
    """``device_dispatch_total``: every kernel dispatch admitted through
    the plane budget, fused or per-stage — the loongresident
    dispatch-count ledger (rate() against batch counts recovers
    dispatches-per-batch, the number stage fusion collapses toward 1).
    Double-checked lock: concurrent first dispatches must not
    double-register the record (the aggregator-base race shape)."""
    global _dispatch_counter
    if _dispatch_counter is None:
        with _dispatch_counter_lock:
            if _dispatch_counter is None:
                from ..monitor.metrics import MetricsRecord
                rec = MetricsRecord(category="component",
                                    labels={"component": "device_plane"})
                _dispatch_counter = rec.counter("device_dispatch_total")
    return _dispatch_counter


_held_hist = None


def held_fraction_histogram():
    """Distribution of the budget fraction held at each dispatch — the
    loongprof utilization view: a histogram living near 1.0 means the
    budget (not the device) gates dispatch."""
    global _held_hist
    if _held_hist is None:
        from ..monitor.metrics import shared_histogram
        _held_hist = shared_histogram("device_budget_held_fraction",
                                      labels={"component": "device_plane"})
    return _held_hist


def note_host_backlog() -> None:
    """loongprof utilization probe, called by runner loops that just
    popped work while more work remains queued: if the device plane sits
    idle even though the host has backlog, the idle gap is charged to
    ``device_idle_while_backlogged_ms`` — the single number separating
    "shard more workers" (host-bound: counter grows) from "the device is
    the bottleneck" (counter flat while occupancy is high).  One global
    read when no plane was ever constructed."""
    plane = DevicePlane._instance
    if plane is not None:
        plane.note_backlogged()


def set_budget_relief(fn: Optional[Callable[[], bool]]) -> None:
    """Register this thread's last-resort budget releaser.  While a thread
    waits for budget in `submit`, the plane first lets the in-dispatch
    DeviceStream window drain its own chunks (`on_wait`); if that owns
    nothing, the relief hook runs — the ProcessorRunner registers one that
    completes the overlapped group it still holds.  Together they enforce
    the no-deadlock invariant: a thread waiting for budget never holds
    unmaterialised futures it cannot release itself."""
    _tls.relief = fn


@contextlib.contextmanager
def budget_relief_first(fn: Callable[[], bool]):
    """While the body runs, ``fn`` is this thread's budget releaser, asked
    before the registered one: a caller that keeps several handles of its
    own in flight at once (processor_grok: a handle a member of ``Match``)
    can give back what the earlier ones hold while a later one waits for
    budget — the no-deadlock invariant of `set_budget_relief`, kept for
    what the runner's hook cannot see."""
    outer = getattr(_tls, "relief", None)
    _tls.relief = lambda: bool(fn()) or (outer is not None and bool(outer()))
    try:
        yield
    finally:
        _tls.relief = outer


_first_dispatch_marked = False


def _mark_first_dispatch() -> None:
    """Start-up phase ``first_dispatch`` (monitor/startup.py): the first
    dispatch of the process has materialised.  One global read after."""
    global _first_dispatch_marked
    _first_dispatch_marked = True
    from ..monitor import startup
    startup.mark("first_dispatch")


def _budget_from_env() -> int:
    try:
        return int(os.environ.get("LOONG_DEVICE_INFLIGHT_BYTES",
                                  _DEFAULT_BUDGET))
    except ValueError:
        return _DEFAULT_BUDGET


def _start_copy_back(outputs: Sequence) -> int:
    """Start the device→host copy of every output that can start one (a
    `jax.Array`, sharded or not): the call only enqueues the transfer
    behind the program and returns, so it lands while the worker packs
    and dispatches the next groups.  Outputs without the method (numpy
    from a host kernel, the latency-injection fakes) are skipped.
    Returns how many copies started.  A start that raises is dropped
    here: `result()` surfaces the buffer's error at the consume point."""
    started = 0
    try:
        for o in outputs:
            start = getattr(o, "copy_to_host_async", None)
            if start is not None:
                start()
                started += 1
    except Exception:  # noqa: BLE001 — fail at consume, not at submit
        pass
    return started


class DeviceFuture:
    """A dispatched kernel call whose results are not yet materialised.

    The copy back of the outputs started when the dispatch was issued
    (`DevicePlane.submit`).  `result()` waits until the device has finished,
    converts the outputs to numpy — picking up the host copies that landed
    meanwhile — and releases the plane budget exactly once.  If the
    kernel raised at dispatch or materialisation, the error is surfaced from
    `result()` so callers keep the reference's fail-at-consume semantics
    (engine.py routes Mosaic failures to the XLA path there).
    """

    __slots__ = ("_plane", "_nbytes", "_outputs", "_error", "_done",
                 "_materialised", "_t0", "_span", "_tenant", "_xid",
                 "__weakref__")

    def __init__(self, plane: "DevicePlane", nbytes: int,
                 outputs: Optional[Sequence] = None,
                 error: Optional[BaseException] = None,
                 span=None, tenant: Optional[str] = None, xid: int = 0):
        self._plane = plane
        self._nbytes = nbytes
        self._outputs = outputs
        self._error = error
        self._done = False
        self._materialised: Optional[List[np.ndarray]] = None
        # the submit→resolve stopwatch starts the moment the dispatched
        # future exists; result()/release() stops it exactly once
        self._t0 = time.perf_counter()
        self._span = span
        # loongtenant: which tenant's share these bytes count against —
        # credited back exactly once when the future settles
        self._tenant = tenant
        # loongxprof: the dispatch id correlating this future's device
        # legs with the host span that caused them (0 = plane off)
        self._xid = xid

    @property
    def dispatch_id(self) -> int:
        """loongxprof correlation id (0 when the timeline is off) — the
        dispatch loops read this to attribute program/geometry/pack legs
        via ``xprof.note_dispatch``."""
        return self._xid

    def _release_budget(self) -> None:
        self._plane._release(self._nbytes)
        if self._tenant is not None:
            _tenant_note(self._tenant, -self._nbytes)
            self._tenant = None
        # settle point: fold this dispatch's legs into the decomposition
        # histograms exactly once (no-op for xid 0 / plane off)
        xprof.close_dispatch(self._xid)

    def result(self) -> List[np.ndarray]:
        if self._done:
            if self._error is not None:
                raise self._error
            return self._materialised  # type: ignore[return-value]
        try:
            if self._error is not None:
                raise self._error
            # loongprof: materialisation is where the host actually waits
            # on the device — attribute that wall time to the device scope
            prof.push_marker("device", "materialise")
            try:
                xid = self._xid
                tracer = trace.active_tracer()
                if xid or tracer is not None:
                    # exec leg / device.wait: dispatch return → first
                    # output ready (the device-execution window the host
                    # can observe); d2h leg / device.d2h: the numpy
                    # materialisation itself — the copy was started at
                    # submit, so this is what of it the host still has
                    # to wait for.  Without a block_until_ready the
                    # split collapses into d2h.  One pair of readings
                    # feeds both planes; the tracer's spans take this
                    # thread's CPU clock at the same three points.
                    t_exec = time.perf_counter()
                    if tracer is not None:
                        c_exec = time.thread_time()
                    first = self._outputs[0] if self._outputs else None
                    if hasattr(first, "block_until_ready"):
                        first.block_until_ready()
                    if tracer is not None:
                        c_d2h = time.thread_time()
                    t_d2h = time.perf_counter()
                    try:
                        self._materialised = [np.asarray(o)
                                              for o in self._outputs]
                    finally:
                        if tracer is not None:
                            c_end = time.thread_time()
                        t_end = time.perf_counter()
                        xprof.leg(xid, "exec", t_exec, t_d2h - t_exec)
                        xprof.leg(xid, "d2h", t_d2h, t_end - t_d2h)
                        if tracer is not None:
                            attrs = xprof.leg_attrs(self._nbytes, xid)
                            tracer.record_timed("device", "device.wait",
                                                t_exec, t_d2h - t_exec,
                                                attrs, c_d2h - c_exec)
                            tracer.record_timed("device", "device.d2h",
                                                t_d2h, t_end - t_d2h, attrs,
                                                c_end - c_d2h)
                else:
                    self._materialised = [np.asarray(o)
                                          for o in self._outputs]
            finally:
                prof.pop_marker()
            roundtrip_histogram().observe(time.perf_counter() - self._t0)
            if self._span is not None:
                self._span.end("ok")
            if not _first_dispatch_marked:
                _mark_first_dispatch()
            return self._materialised
        except BaseException as e:  # noqa: BLE001 — record, release, re-raise
            self._error = e
            if self._span is not None:
                self._span.end("error")
            raise
        finally:
            self._done = True
            self._outputs = None
            self._span = None
            self._release_budget()

    def release(self) -> None:
        """Force-release without materialising: error-path cleanup for a
        dispatch loop that cannot (or must not) consume this future.  The
        device buffers are dropped; the budget returns immediately."""
        if self._done:
            return
        self._done = True
        self._outputs = None
        if self._error is None:
            self._error = RuntimeError(
                "DeviceFuture released without materialisation")
        if self._span is not None:
            self._span.end("released")
            self._span = None
        self._release_budget()

    def __del__(self):
        # Last-resort budget backstop: an abandoned in-flight future must
        # never strand plane budget (the round-5 PendingParse.dispatch
        # leak).  Reaching this path is a bug upstream — warn loudly.
        try:
            if not self._done:
                self._done = True
                self._outputs = None
                if self._span is not None:
                    self._span.end("abandoned")
                    self._span = None
                self._release_budget()
                log.warning(
                    "DeviceFuture dropped without result()/release(); "
                    "budget (%d bytes) reclaimed by finaliser — fix the "
                    "owning dispatch path", self._nbytes)
        except Exception:  # noqa: BLE001 — never raise from a finaliser
            pass


class DevicePlane:
    """Process-wide async dispatch gate with an in-flight byte budget."""

    _instance: Optional["DevicePlane"] = None
    _instance_lock = threading.Lock()

    def __init__(self, budget_bytes: Optional[int] = None):
        self.budget_bytes = budget_bytes or _budget_from_env()
        self._inflight = 0
        self._dispatched = 0
        self._prefetched = 0   # dispatches whose copy back began at submit
        self._h2d_arrays = 0   # arrays handed to the dispatched calls
        self._d2h_arrays = 0   # outputs whose copy back was started
        self._lock = threading.Lock()
        self._freed = threading.Condition(self._lock)
        self._closed = False
        # -- loongprof utilization accounting (all under self._lock) --------
        now = time.perf_counter()
        self._util_t0 = now                 # accounting epoch
        self._util_last = now               # last occupancy transition
        self._occupancy_integral = 0.0      # ∫ (inflight/budget) dt
        self._busy_s = 0.0                  # time with inflight > 0
        self._idle_since: Optional[float] = now
        self._idle_backlogged_ms = 0.0
        self._backlog_probe_at: Optional[float] = None
        self._waiters = 0                   # threads blocked in _acquire

    @classmethod
    def instance(cls) -> "DevicePlane":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def reset_for_testing(cls, budget_bytes: Optional[int] = None) -> "DevicePlane":
        with cls._instance_lock:
            cls._instance = cls(budget_bytes)
            return cls._instance

    # -- budget -------------------------------------------------------------

    def inflight_bytes(self) -> int:
        with self._lock:
            return self._inflight

    def dispatched_total(self) -> int:
        with self._lock:
            return self._dispatched

    def over_budget(self) -> bool:
        with self._lock:
            return self._inflight >= self.budget_bytes

    def would_block(self, nbytes: int) -> bool:
        """True when submit(nbytes) would have to wait for budget.  Dispatch
        loops that hold unmaterialised futures MUST consult this and drain
        their own oldest future first — never sleep in submit while owning
        the budget you are waiting for."""
        with self._lock:
            return (self._inflight + nbytes > self.budget_bytes
                    and self._inflight > 0)

    # -- utilization accounting (loongprof) ---------------------------------

    def _util_tick(self, now: float) -> None:
        """Lock held.  Fold the elapsed interval into the occupancy
        integrals BEFORE an inflight transition."""
        dt = now - self._util_last
        if dt > 0:
            self._occupancy_integral += (self._inflight / self.budget_bytes
                                         if self.budget_bytes else 0.0) * dt
            if self._inflight > 0:
                self._busy_s += dt
        self._util_last = now

    def note_backlogged(self) -> None:
        """The host has queued work RIGHT NOW (caller just popped an item
        with more behind it).  Charge the device-idle gap SINCE THE LAST
        backlogged probe to ``device_idle_while_backlogged_ms`` — the
        first probe of an idle span only arms the window, so the hour the
        agent sat idle with no traffic is never charged when a burst
        finally arrives (backlog must exist at BOTH ends of a charged
        gap).  Planes that never dispatched stay at zero — a pure-host
        pipeline's idle device is not a finding."""
        now = time.perf_counter()
        with self._lock:
            if self._dispatched == 0 or self._inflight > 0 \
                    or self._idle_since is None:
                self._backlog_probe_at = None
                return
            if self._backlog_probe_at is None:
                self._backlog_probe_at = now
                return
            start = max(self._idle_since, self._backlog_probe_at)
            if now > start:
                self._idle_backlogged_ms += (now - start) * 1000.0
            self._backlog_probe_at = now

    def utilization(self) -> dict:
        """Snapshot of the device-plane utilization accounting — the
        "shard more vs device-bound" dashboard (docs/observability.md)."""
        now = time.perf_counter()
        with self._lock:
            self._util_tick(now)
            elapsed = max(now - self._util_t0, 1e-9)
            return {
                "budget_bytes": self.budget_bytes,
                "inflight_bytes": self._inflight,
                "held_fraction": (self._inflight / self.budget_bytes
                                  if self.budget_bytes else 0.0),
                "occupancy_avg": self._occupancy_integral / elapsed,
                # time with bytes in flight over wall time: the budget's
                # view, not the chip's (a dispatch is "in flight" from
                # submit until the host materialises it)
                "inflight_fraction": self._busy_s / elapsed,
                # raw monotone integrals: lifetime averages go inert on a
                # long-lived agent, but rate() over these recovers the
                # RECENT occupancy/busy fraction from any scrape pair
                "occupancy_integral_s": self._occupancy_integral,
                "inflight_s": self._busy_s,
                "idle_while_backlogged_ms": self._idle_backlogged_ms,
                "submit_queue_depth": self._waiters,
                "dispatched_total": self._dispatched,
                "d2h_prefetched_total": self._prefetched,
                # what crosses per dispatch, in arrays: each one is a
                # transfer (or a copy start and an np.asarray) of its own
                "h2d_arrays_total": self._h2d_arrays,
                "d2h_arrays_total": self._d2h_arrays,
                "elapsed_s": elapsed,
            }

    def _acquire(self, nbytes: int,
                 should_abort: Optional[Callable[[], bool]] = None,
                 on_wait: Optional[Callable[[], bool]] = None) -> int:
        """Block until `nbytes` fits in the budget.  A single dispatch larger
        than the whole budget is admitted when nothing is in flight (it could
        otherwise never run).  This blocking IS the device back-pressure: the
        caller is a runner thread, and while it waits the bounded process
        queues upstream fill to their high watermark.

        `on_wait` is called OUTSIDE the lock on every wait iteration; a
        caller that owns unmaterialised futures must drain one there and
        return True (False = nothing owned).  That rule makes the budget
        deadlock-free: every waiting thread can always release the budget it
        itself holds, so some thread always makes progress."""
        waiting = False
        wait_sp = None
        try:
            while True:
                with self._freed:
                    if self._closed or \
                            self._inflight + nbytes <= self.budget_bytes or \
                            self._inflight == 0:
                        self._util_tick(time.perf_counter())
                        self._inflight += nbytes
                        self._dispatched += 1
                        self._idle_since = None
                        # post-admission inflight, returned so the caller
                        # can observe THIS dispatch's held fraction without
                        # re-taking the lock (a later read would race
                        # concurrent releases)
                        return self._inflight
                    if should_abort is not None and should_abort():
                        raise DispatchAborted()
                    if not waiting:
                        # submit-queue depth: threads blocked on budget —
                        # sustained depth > 0 with high occupancy means the
                        # budget (or the device behind it) gates the host
                        waiting = True
                        self._waiters += 1
                        tracer = trace.active_tracer()
                        if tracer is not None:
                            # only a dispatch that really blocks gets the
                            # span; what on_wait drains nests under it
                            wait_sp = tracer.start_stage(
                                "device", "device.acquire",
                                {"nbytes": nbytes, "on": "budget"})
                progressed = on_wait() if on_wait is not None else False
                if not progressed:
                    relief = getattr(_tls, "relief", None)
                    progressed = bool(relief()) if relief is not None \
                        else False
                if not progressed:
                    with self._freed:
                        self._freed.wait(timeout=0.05)
        finally:
            if wait_sp is not None:
                wait_sp.end()
            if waiting:
                with self._lock:
                    self._waiters -= 1

    def _release(self, nbytes: int) -> None:
        with self._freed:
            self._util_tick(time.perf_counter())
            self._inflight = max(0, self._inflight - nbytes)
            if self._inflight == 0:
                self._idle_since = self._util_last
                self._backlog_probe_at = None
            self._freed.notify_all()

    def close(self) -> None:
        with self._freed:
            self._closed = True
            self._freed.notify_all()

    # -- dispatch -----------------------------------------------------------

    def open_stream(self, depth: Optional[int] = None, **owner):
        """A pipelined dispatch window over this plane (loongstream): up to
        ``depth`` batches in flight, strict submit-order results, ring
        advance on overflow — the streaming replacement for the
        submit→materialise round trip.  ``owner`` is what the dispatcher
        that owns the window tells it (program tag, lane, recovery, …).
        See ops/device_stream.DeviceStream."""
        from .device_stream import DeviceStream
        return DeviceStream(self, depth, **owner)

    def submit(self, kernel: Callable, args: Sequence, nbytes: int,
               should_abort: Optional[Callable[[], bool]] = None,
               on_wait: Optional[Callable[[], bool]] = None
               ) -> DeviceFuture:
        """Dispatch `kernel(*args)` asynchronously under the byte budget.

        Returns a DeviceFuture immediately (the device computes in the
        background).  A kernel that raises AT DISPATCH produces an errored
        future rather than raising here, so a multi-chunk dispatch loop keeps
        its bookkeeping simple and errors surface at the (ordered)
        materialisation point."""
        tenant = getattr(_tls, "tenant", None)
        tracer = trace.active_tracer()
        if tenant is not None and on_wait is not None:
            # per-tenant budget share (loongtenant): a tenant already past
            # budget/n_tenants drains ITS OWN oldest in-flight chunk before
            # dispatching more.  Other tenants never enter this loop — one
            # hot pipeline's backlog costs only that pipeline latency
            wait_sp = None
            try:
                while tenant_over_share(tenant, nbytes, self.budget_bytes):
                    if wait_sp is None and tracer is not None:
                        wait_sp = tracer.start_stage(
                            "device", "device.acquire",
                            {"nbytes": nbytes, "on": "tenant_share"})
                    if not on_wait():
                        break
            finally:
                if wait_sp is not None:
                    wait_sp.end()
        inflight_now = self._acquire(nbytes, should_abort, on_wait)
        if tenant is not None:
            _tenant_note(tenant, nbytes)
        dispatch_counter().add(1)
        if self.budget_bytes:
            held_fraction_histogram().observe(
                inflight_now / self.budget_bytes)
        span = None
        if tracer is not None:
            # the round trip outlives the stage that submits it: it hangs
            # from the group's root, so `.dispatch` keeps its self time
            root = tracer.root_span()
            # it is a stopwatch from submit to result — the thread works
            # on other groups meanwhile — so it takes no CPU reading
            span = (tracer.start_span("device.roundtrip", parent=root,
                                      attrs={"nbytes": nbytes}, cpu=False)
                    if root is not None else
                    tracer.child_or_sampled("device", "device.roundtrip",
                                            {"nbytes": nbytes}, cpu=False))
        # loongxprof: mint the dispatch id AFTER budget admission, so the
        # submit leg measures the dispatch call, not the back-pressure
        # wait (the tracer's device.acquire span covers that).  0 when off.
        xid = xprof.begin_dispatch(nbytes)
        if xid and span is not None:
            # the host/device correlation key the timeline export lines
            # spans up by (volatile attr: excluded from structure)
            span.set_attr("dispatch_id", xid)
        try:
            # after _acquire, inside the try: an injected fault behaves
            # exactly like a kernel raising at dispatch — errored future,
            # budget released at the consume point (result/release)
            chaos.faultpoint(FP_SUBMIT)
            prof.push_marker("device", "dispatch")
            timed = bool(xid) or tracer is not None
            if xid:
                # current-dispatch TLS: code running INSIDE the kernel
                # call (ShardedKernel._dispatch) attaches its H2D legs to
                # this dispatch
                xprof.set_current_dispatch(xid)
            if timed:
                t_submit = time.perf_counter()
                if tracer is not None:
                    c_submit = time.thread_time()
            try:
                outputs = kernel(*args)
                if not isinstance(outputs, (tuple, list)):
                    outputs = (outputs,)
                started = _start_copy_back(outputs)
                with self._lock:
                    self._h2d_arrays += len(args)
                    self._d2h_arrays += started
                    if outputs and started == len(outputs):
                        self._prefetched += 1
            finally:
                if timed:
                    # submit leg / device.submit: the dispatch call and
                    # the start of the copy back — one pair of readings
                    # for both planes
                    if tracer is not None:
                        dc_submit = time.thread_time() - c_submit
                    dt_submit = time.perf_counter() - t_submit
                    if xid:
                        xprof.leg(xid, "submit", t_submit, dt_submit)
                        xprof.set_current_dispatch(0)
                    if tracer is not None:
                        tracer.record_timed("device", "device.submit",
                                            t_submit, dt_submit,
                                            xprof.leg_attrs(nbytes, xid),
                                            dc_submit)
                prof.pop_marker()
            return DeviceFuture(self, nbytes, outputs=outputs, span=span,
                                tenant=tenant, xid=xid)
        except DispatchAborted:
            if span is not None:
                span.end("aborted")
            self._release(nbytes)
            if tenant is not None:
                _tenant_note(tenant, -nbytes)
            xprof.close_dispatch(xid)
            raise
        except BaseException as e:  # noqa: BLE001 — deliver via result()
            return DeviceFuture(self, nbytes, error=e, span=span,
                                tenant=tenant, xid=xid)


class DispatchAborted(RuntimeError):
    """Raised by submit() when the caller's should_abort() fired while
    waiting for budget (pipeline stopping)."""


# ---------------------------------------------------------------------------
# Latency-injection kernel: the CPU-testable stand-in for a slow device
# (a test fake — nothing on the served path constructs one).


class LatencyInjectedArray:
    """Numpy-convertible handle that blocks until a deadline — models an
    async device buffer whose computation completes `rtt` after dispatch."""

    __slots__ = ("_value", "_deadline")

    def __init__(self, value: np.ndarray, deadline: float):
        self._value = value
        self._deadline = deadline

    def block_until_ready(self) -> "LatencyInjectedArray":
        now = time.perf_counter()
        if now < self._deadline:
            time.sleep(self._deadline - now)
        return self

    def __array__(self, dtype=None, copy=None):
        self.block_until_ready()
        if dtype is not None:
            return self._value.astype(dtype)
        return self._value


class LatencyInjectedKernel:
    """Wraps a synchronous kernel so that dispatch returns instantly and
    materialisation blocks for `rtt_s` — a model of a slow
    accelerator.  `serialize=True` (concurrency 1) models a
    device that executes one dispatch at a time: each call's execution
    starts after the previous call's, exactly like a device execution
    stream.

    ``wire_s`` splits a slow round trip into its pipelinable part:
    each dispatch pays one-way wire latency BEFORE execution can start
    (H2D) and the host pays it again before results are visible (D2H), so
    a synchronous round trip costs ``2*wire_s + rtt_s`` while a pipelined
    dispatcher overlaps the wire legs of neighbouring batches and is
    bounded only by the serialized execution stream (``rtt_s`` per batch).
    This is what the loongstream depth sweep measures.  wire_s=0 keeps the
    original single-latency behaviour."""

    def __init__(self, inner: Callable, rtt_s: float, serialize: bool = True,
                 wire_s: float = 0.0):
        self.inner = inner
        self.rtt_s = rtt_s
        self.serialize = serialize
        self.wire_s = wire_s
        self._stream_free_at = 0.0
        self._lock = threading.Lock()
        self.calls = 0

    def __call__(self, *args):
        outs = self.inner(*args)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        now = time.perf_counter()
        with self._lock:
            self.calls += 1
            if self.serialize:
                # execution may start once the batch has crossed the wire
                # AND the single execution stream is free
                start = max(now + self.wire_s, self._stream_free_at)
                exec_done = start + self.rtt_s
                self._stream_free_at = exec_done
            else:
                exec_done = now + self.wire_s + self.rtt_s
            deadline = exec_done + self.wire_s   # results cross back
        return tuple(LatencyInjectedArray(np.asarray(o), deadline)
                     for o in outs)


class StallableKernel(LatencyInjectedKernel):
    """Latency kernel whose completions can be held indefinitely — for
    watermark-under-stalled-device tests."""

    def __init__(self, inner: Callable, rtt_s: float = 0.0):
        super().__init__(inner, rtt_s)
        self._stalled = threading.Event()
        self._stalled.set()  # set = running

    def stall(self) -> None:
        self._stalled.clear()

    def unstall(self) -> None:
        self._stalled.set()

    def __call__(self, *args):
        outs = super().__call__(*args)
        ev = self._stalled

        class _Gate(LatencyInjectedArray):
            __slots__ = ()

            def block_until_ready(self):
                ev.wait()
                return super().block_until_ready()

        return tuple(_Gate(o._value, o._deadline) for o in outs)
