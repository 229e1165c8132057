"""ProcessorRunner: the sharded multi-worker processing engine (loongshard).

Reference: core/runner/ProcessorRunner.cpp — N worker threads (default 1,
app_config/AppConfig.cpp:58) pop from the process-queue manager (priority RR),
find the owning pipeline, run Process then Send (:90-189); thread 0 also
pumps batch timeout flushes (:109-112); producer API PushQueue with bounded
retries (:72-88).

loongshard (ISSUE 4) makes thread_count real without giving up ordering:

* ``thread_count == 1`` keeps the reference shape — one worker popping the
  process-queue manager directly.
* ``thread_count > 1`` adds a dispatch loop that pops the queue manager and
  routes every group to a fixed worker by affinity hash on
  (process queue key, ``__source__`` tag).  All groups of one source stream
  land on one worker, and each worker is a single thread that sends groups
  in pop order — per-source ordering is preserved while distinct sources
  (and distinct pipelines) process in parallel.  The hash is CRC32, stable
  across runs and processes (PYTHONHASHSEED-proof), so a replayed soak
  shards identically.
* Worker inboxes are small and bounded: when a worker falls behind, the
  dispatcher blocks on its inbox, stops popping, and the bounded process
  queues fill to their high watermark — the same feedback chain as before,
  one hop longer.

TPU note — the async device data plane (SURVEY §7 step 4), now streaming
(loongstream, ISSUE 6): each worker owns ONE WorkerLane — a FIFO ring
holding up to ``LOONG_STREAM_DEPTH - 1`` groups whose device work is in
flight.  The worker dispatches group N+1 (host pre-processing + ring-slot
pack + async kernel dispatch via Pipeline.process_begin), then advances the
ring: the OLDEST pending group (N-depth+1) materialises and sends while the
device computes the newer ones — pack/H2D of N+1 overlaps compute of N and
span-return of N-1.  The auto-tuner's flush deadline bounds how long a
group may ride the ring, so trickle traffic keeps interactive latency.
Device back-pressure is the DevicePlane in-flight byte budget: when the
device stalls, dispatch blocks, the worker stops consuming, its inbox
fills, the dispatcher stops popping, and the bounded process queues
feedback-block the inputs.  Every worker registers a budget-relief hook
bound to ITS lane, so a worker waiting for budget always completes the
oldest overlapped group it owns (no-deadlock invariant, per lane; FIFO, so
relief never reorders sends).

A group may hold device work at more than one stage of its chain (PR 31:
the multiline classify, then the record extract).  Its continuation then
hands back a continuation: a ring step (``_step_oldest``) materialises the
stage in flight and walks the chain on — to the send, and the group leaves
the ring, or to the next device stage, and it keeps its place.  The ring
stays in pop order and only its head is ever sent, so send order is pop
order whatever the stages; when a group leaves from a later stage, the one
behind it moves up a stage in the same turn (the staircase in
``_advance_ring``), so every stage has a turn in flight before anyone waits
for it.  A chain with one device stage walks as it always did.
"""

from __future__ import annotations

import os
import threading
import time
import zlib
from collections import deque
from typing import List, Optional, Tuple

from .. import prof, trace
from ..models import EventGroupMetaKey, PipelineEventGroup
from ..monitor import ledger, slo
from ..monitor.alarms import AlarmLevel, AlarmManager, AlarmType
from ..monitor.metrics import MetricsRecord
from ..ops import chip_lanes
from ..ops.device_plane import (current_tenant, note_host_backlog,
                                set_budget_relief, set_thread_tenant)
from ..ops.device_stream import auto_tuner
from . import ack_watermark
from ..prof import flight
from ..pipeline.batch.timeout_flush_manager import TimeoutFlushManager
from ..pipeline.queue.process_queue_manager import (RUN_MAX_GROUPS,
                                                    ProcessQueueManager)
from ..utils import flags
from ..utils.logger import get_logger

log = get_logger("processor_runner")

BATCH_FLUSH_INTERVAL_S = 1.0

# loongshard default: scale past one worker out of the box, but never spawn
# more shards than the host can run (the reference default of 1 mirrored the
# pre-shard engine)
DEFAULT_PROCESS_THREADS = max(2, min(4, os.cpu_count() or 2))

flags.DEFINE_FLAG_INT32("process_thread_count",
                        "processor runner worker shards",
                        DEFAULT_PROCESS_THREADS)

ENV_THREADS = "LOONG_PROCESS_THREADS"

# observe-only handle for the self-monitor (monitor/runtime_stats.py):
# the live runner's shard state without constructing anything
_active_runner = None


def resolve_thread_count(env=os.environ) -> int:
    """Active worker count: ``LOONG_PROCESS_THREADS`` wins over the
    ``process_thread_count`` flag (itself overridable by app config and
    ``LOONG_PROCESS_THREAD_COUNT``); anything invalid or < 1 falls back,
    and the result is always >= 1."""
    raw = env.get(ENV_THREADS)
    if raw is not None:
        try:
            n = int(raw)
            if n >= 1:
                return n
            log.warning("%s=%r below 1; using flag", ENV_THREADS, raw)
        except ValueError:
            log.warning("invalid %s=%r; using flag", ENV_THREADS, raw)
    return max(1, int(flags.get_flag("process_thread_count")))

# per-worker inbox depth: small on purpose — the real buffering lives in the
# bounded process queues; the inbox only smooths the dispatch hop
INBOX_CAPACITY = 4

_SOURCE_TAG = b"__source__"


def shard_of(queue_key: int, source: Optional[bytes], n: int) -> int:
    """Affinity shard for a group: CRC32 over the source identity seeded
    with the process queue key.  Deterministic across processes (no Python
    hash randomisation) so replayed storms shard identically."""
    if n <= 1:
        return 0
    return zlib.crc32(source or b"", queue_key & 0xFFFFFFFF) % n


def group_source_id(group: PipelineEventGroup) -> Optional[bytes]:
    """The per-source ordering identity of a group: the ``__source__`` tag
    when an input sets one, else the originating file (path + inode — two
    rotated generations of one path may interleave but each stream keeps a
    stable home), else None.  Unkeyed groups of one pipeline all land on one
    worker — ordering-safe by construction."""
    src = group.get_tag(_SOURCE_TAG)
    if src is not None:
        return src.to_bytes()
    path = group.get_metadata(EventGroupMetaKey.LOG_FILE_PATH)
    if path is not None:
        inode = group.get_metadata(EventGroupMetaKey.LOG_FILE_INODE)
        pid = path.to_bytes()
        return (pid + b":" + inode.to_bytes()) if inode is not None else pid
    return None


class _LaneEntry:
    """One group in a lane ring: its pending tuple (``finish`` is the
    continuation of the stage in flight, None once the chain is done and
    only the send is owed), when it entered, how many stages it has
    advanced, and whether a step holds it right now."""

    __slots__ = ("pending", "at", "stage", "out", "gone")

    def __init__(self, pending, at: float):
        self.pending = pending
        self.at = at
        self.stage = 0
        self.out = False
        self.gone = False


class WorkerLane:
    """One worker's overlapped-dispatch ring (its device lane).

    loongstream: up to ``depth - 1`` groups' device work stays in flight
    per worker (``LOONG_STREAM_DEPTH``, default 3 ⇒ two pending groups
    while a third packs/dispatches).  The ring is strict FIFO in pop
    order.  A step checks the oldest free entry out (``check_out``), runs
    it, and checks it back in: gone (sent, or dropped on an error), or in
    its place with its next continuation.  The worker loop and the
    DevicePlane budget-relief hook both step — the hook from inside a
    step's own dispatch — so an entry is held by one step at a time, and
    an entry stepped while an older one is still out must not be sent:
    it parks with its chain done until it is the head.  Completion (send)
    order therefore always matches dispatch (pop) order: per-source
    ordering survives any depth and any number of device stages.
    ``take()`` is the one-stage form of the same thing — remove and return
    the OLDEST pending atomically.
    ``oldest_age()`` drives the auto-tuner's flush deadline — a pending
    group never rides the ring past it, bounding batch latency when the
    queue trickles."""

    __slots__ = ("worker_id", "depth", "capacity", "_lock", "_pending",
                 "_t0", "_held_since", "_held_s")

    def __init__(self, worker_id: int, depth: Optional[int] = None):
        from ..ops.device_stream import stream_depth
        self.worker_id = worker_id
        self.depth = depth if depth is not None else stream_depth()
        self.capacity = max(1, self.depth - 1)
        self._lock = threading.Lock()
        self._pending: deque = deque()   # [_LaneEntry], pop order
        # loongprof: overlap accounting — how long this lane held a group
        # whose device work was in flight, over the lane's lifetime
        self._t0 = time.perf_counter()
        self._held_since = 0.0
        self._held_s = 0.0

    def put(self, pending) -> None:
        if pending is None:
            return
        now = time.perf_counter()
        with self._lock:
            assert len(self._pending) < self.capacity, "lane ring full"
            if not self._pending:
                self._held_since = now
            self._pending.append(_LaneEntry(pending, now))

    def _remove(self, ent: _LaneEntry) -> None:
        # caller holds the lock
        self._pending.remove(ent)
        ent.gone = True
        if not self._pending:
            self._held_s += time.perf_counter() - self._held_since

    def take(self):
        """Remove and return the OLDEST pending entry (FIFO — the ring
        advance), or None."""
        with self._lock:
            if not self._pending or self._pending[0].out:
                return None
            ent = self._pending[0]
            self._remove(ent)
            return ent.pending

    def check_out(self):
        """``(entry, hold_send)`` for the oldest entry a step may run, or
        None: entries an outer step holds are passed over, and what sits
        behind one may advance but not be sent (``hold_send``) — a parked
        entry behind one has nothing left to do until it is the head."""
        with self._lock:
            behind = False
            for ent in self._pending:
                if ent.out:
                    behind = True
                    continue
                if behind and ent.pending[2] is None:
                    continue
                ent.out = True
                return ent, behind
            return None

    def check_in(self, ent: _LaneEntry, pending) -> None:
        """End of a step: ``pending`` is what stays in the entry's place
        (its next stage in flight, or parked), None when the group left."""
        with self._lock:
            ent.out = False
            if pending is None:
                self._remove(ent)
            else:
                ent.pending = pending
                ent.stage += 1

    def oldest_stage(self) -> Optional[int]:
        with self._lock:
            return self._pending[0].stage if self._pending else None

    def busy(self) -> bool:
        with self._lock:
            return bool(self._pending)

    def full(self) -> bool:
        with self._lock:
            return len(self._pending) >= self.capacity

    def pending_count(self) -> int:
        with self._lock:
            return len(self._pending)

    def oldest_age(self) -> Optional[float]:
        """Seconds the oldest pending group has ridden the ring (None when
        empty) — compared against the auto-tuner's flush deadline."""
        with self._lock:
            if not self._pending:
                return None
            return time.perf_counter() - self._pending[0].at

    def overlap_ratio(self) -> float:
        """Fraction of this lane's lifetime spent with device work in
        flight — near 0 means the worker never overlaps (host-bound or
        idle), near 1 means the lane is saturated (device-bound)."""
        now = time.perf_counter()
        with self._lock:
            held = self._held_s
            if self._pending:
                held += now - self._held_since
        elapsed = max(now - self._t0, 1e-9)
        return held / elapsed


class _ShardInbox:
    """Bounded SPSC handoff between the dispatch loop and one worker.
    A full inbox blocks the dispatcher (back-pressure); ``close()`` wakes
    the worker for final drain."""

    def __init__(self, capacity: int = INBOX_CAPACITY):
        self._items: deque = deque()
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._capacity = capacity
        self._closed = False

    def put(self, item, timeout: float = 1.0) -> bool:
        """Blocks while full.  Returns False only when closed (caller then
        owns the item again) or the wait timed out with no space."""
        deadline = time.monotonic() + timeout
        with self._not_full:
            while len(self._items) >= self._capacity and not self._closed:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._not_full.wait(remaining)
            if self._closed:
                return False
            self._items.append(item)
            self._not_empty.notify()
            return True

    def get(self, timeout: float = 0.2):
        with self._not_empty:
            if not self._items:
                if timeout > 0 and not self._closed:
                    self._not_empty.wait(timeout)
                if not self._items:
                    return None
            item = self._items.popleft()
            self._not_full.notify()
            return item

    def get_run(self, timeout: float = 0.2, max_groups: int = 8):
        """Backlog-aware drain (loongcolumn): pop the head item plus any
        consecutive items sharing its queue key, as one (key, groups) run —
        FIFO order preserved, so per-source ordering is untouched.  A
        trickle yields single-group runs; a backlog amortises the worker's
        per-dispatch hand-off."""
        with self._not_empty:
            if not self._items:
                if timeout > 0 and not self._closed:
                    self._not_empty.wait(timeout)
                if not self._items:
                    return None
            key, group = self._items.popleft()
            groups = [group]
            while self._items and len(groups) < max_groups \
                    and self._items[0][0] == key:
                groups.append(self._items.popleft()[1])
            self._not_full.notify_all()
            return key, groups

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()

    def is_closed(self) -> bool:
        with self._lock:
            return self._closed

    def drained(self) -> bool:
        with self._lock:
            return self._closed and not self._items

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


class ProcessorRunner:
    def __init__(self, process_queue_manager: ProcessQueueManager,
                 pipeline_manager, thread_count: Optional[int] = None,
                 run_max_groups: Optional[int] = None):
        self.pqm = process_queue_manager
        self.pipeline_manager = pipeline_manager
        if thread_count is None:
            thread_count = resolve_thread_count()
        self.thread_count = max(1, int(thread_count))
        # loongcolumn backlog-aware pops: how many same-pipeline groups one
        # pop may hand a worker (1 = the pre-run per-group shape;
        # LOONG_POP_RUN overrides for experiments)
        if run_max_groups is None:
            try:
                run_max_groups = int(os.environ.get("LOONG_POP_RUN", "0")) \
                    or RUN_MAX_GROUPS
            except ValueError:
                run_max_groups = RUN_MAX_GROUPS
        self.run_max_groups = max(1, int(run_max_groups))
        self._threads: List[threading.Thread] = []
        self._dispatch_thread: Optional[threading.Thread] = None
        self._lanes: List[WorkerLane] = []
        self._inboxes: List[_ShardInbox] = []
        self._running = False
        # loongledger: groups popped from a queue/inbox but not yet
        # anchored in another occupancy counter (inbox / lane /
        # _in_process_cnt) — covers the hop so a descheduled worker
        # holding a group in a local variable cannot fake a quiesce.
        # Known residual sliver: the increment runs just AFTER the pop
        # returns (holding it across the blocking wait would count idle
        # workers as inflight and the auditor would never quiesce), so a
        # thread descheduled for 2+ audit intervals in the few
        # instructions between B_DEQUEUE and _note_in_hand(1) could still
        # slip the probe; the two-consecutive-quiesced-audits confirmation
        # is the backstop for that nanosecond window
        self._in_hand = 0
        self._in_hand_lock = threading.Lock()
        self.metrics = MetricsRecord(category="runner",
                                     labels={"runner": "processor"})
        self.in_groups = self.metrics.counter("in_event_groups_total")
        self.in_events = self.metrics.counter("in_events_total")
        self.in_bytes = self.metrics.counter("in_size_bytes")
        # active worker count: the exposition endpoint / self-monitor report
        # how many shards this agent actually runs (ISSUE 4 satellite)
        self.workers_gauge = self.metrics.gauge("process_workers")
        # pop → send-returned latency per group (process + device overlap +
        # downstream processors + route/flush enqueue); queue wait is its
        # own histogram on the process-queue side
        self.e2e_hist = self.metrics.histogram("pipeline_e2e_seconds")
        self.last_flush = time.monotonic()
        # every worker/dispatcher loop pumps the flush cadence: claiming
        # the interval must be atomic or two shards double-flush
        self._flush_claim = threading.Lock()

    # -- producer API -------------------------------------------------------

    def push_queue(self, key: int, group: PipelineEventGroup,
                   retry_times: int = 10) -> bool:
        for _ in range(retry_times):
            if self.pqm.push_queue(key, group):
                return True
            time.sleep(0.01)
        AlarmManager.instance().send_alarm(
            AlarmType.PROCESS_QUEUE_FULL,
            f"push rejected after {retry_times} retries (queue {key})",
            AlarmLevel.WARNING)
        return False

    # -- lifecycle ----------------------------------------------------------

    def init(self) -> None:
        global _active_runner
        self._running = True
        self._lanes = [WorkerLane(i) for i in range(self.thread_count)]
        self.workers_gauge.set(self.thread_count)
        _active_runner = self
        if self.thread_count == 1:
            t = threading.Thread(target=self._run_single, args=(0,),
                                 name="processor-0", daemon=True)
            t.start()
            self._threads.append(t)
            return
        self._inboxes = [_ShardInbox() for _ in range(self.thread_count)]
        for i in range(self.thread_count):
            t = threading.Thread(target=self._run_worker, args=(i,),
                                 name=f"processor-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        self._dispatch_thread = threading.Thread(
            target=self._run_dispatch, name="processor-dispatch", daemon=True)
        self._dispatch_thread.start()

    def inbox_depths(self) -> List[int]:
        """Queued groups per worker inbox (empty list when single-worker:
        the reference shape has no dispatch hop to observe)."""
        return [len(ib) for ib in self._inboxes]

    def _note_in_hand(self, delta: int) -> None:
        # clamped at zero: the ledger can come on mid-run, making the
        # first decrement unpaired — never let that offset real occupancy
        with self._in_hand_lock:
            self._in_hand = max(0, self._in_hand + delta)

    def in_hand_count(self) -> int:
        """Groups currently between a queue/inbox pop and their next
        counted station — the ledger's live-occupancy probe."""
        with self._in_hand_lock:
            return self._in_hand

    def lane_overlap(self) -> List[float]:
        """Per-lane device-overlap ratio (loongprof utilization): the
        fraction of each worker's lifetime its lane held in-flight device
        work.  Uniformly low with a growing
        ``device_idle_while_backlogged_ms`` counter says "shard more";
        uniformly high says the device is the bottleneck."""
        return [lane.overlap_ratio() for lane in self._lanes]

    def stop(self) -> None:
        global _active_runner
        if _active_runner is self:
            _active_runner = None
        self._running = False
        self.pqm.wake_up()
        if self._dispatch_thread is not None:
            # the dispatch loop drains the process queues into the inboxes
            # and closes them; workers exit after their final drain
            self._dispatch_thread.join(timeout=10)
            if self._dispatch_thread.is_alive():
                # wedged dispatch must not wedge stop(): close inboxes so
                # workers can still finish what they already hold
                for ib in self._inboxes:
                    ib.close()
            self._dispatch_thread = None
        for t in self._threads:
            t.join(timeout=5)
        self._threads.clear()
        # inboxes/lanes stay allocated (closed): a dispatch thread that
        # out-lived its join timeout may still call _route — an empty list
        # there would IndexError it mid-drain; init() rebuilds both
        # a stopped runner exports nothing further; its record must not
        # accumulate in WriteMetrics across restarts (loonglint
        # metric-naming ownership rule)
        self.metrics.mark_deleted()

    # -- shard routing ------------------------------------------------------

    def _shard(self, key: int, group: PipelineEventGroup) -> int:
        return shard_of(key, group_source_id(group), self.thread_count)

    def _pump_timeout_flush(self) -> None:
        now = time.monotonic()
        with self._flush_claim:
            claimed = now - self.last_flush >= BATCH_FLUSH_INTERVAL_S
            if claimed:
                self.last_flush = now
        # flush outside the claim: only the interval arithmetic needs
        # atomicity, the hooks below take their own locks
        if claimed:
            try:
                TimeoutFlushManager.instance().flush_timeout_batches()
            except Exception:  # noqa: BLE001 — a bad hook must not kill
                # the thread pumping all timeout flushing agent-wide
                log.exception("timeout flush failed")
            try:
                # loongstream: the width auto-tuner re-reads the device
                # utilization accounting on the same 1 s cadence and moves
                # the lane-ring flush deadline (observe-only, fail-soft)
                auto_tuner().maybe_adjust()
            except Exception:  # noqa: BLE001
                log.exception("stream tuner adjust failed")

    def _run_dispatch(self) -> None:
        """Sharded mode only: pop the queue manager, route by affinity.
        Also pumps timeout flushes (the reference's thread-0 duty).
        Pops are backlog-aware runs (loongcolumn): one lock cycle hands
        the dispatcher up to RUN_MAX_GROUPS same-pipeline groups, each
        still routed to its affinity shard individually."""
        while self._running:
            self._pump_timeout_flush()
            run = self.pqm.pop_run(timeout=0.2,
                                   max_groups=self.run_max_groups)
            if run is None:
                continue
            self._handle_routed_run(run)
        # drain remaining items on stop: keep affinity so ordering holds
        # through shutdown too
        while True:
            run = self.pqm.pop_run(timeout=0,
                                   max_groups=self.run_max_groups)
            if run is None:
                break
            self._handle_routed_run(run)
        for ib in self._inboxes:
            ib.close()

    def _handle_routed_run(self,
                           run: Tuple[int, List[PipelineEventGroup]]) -> None:
        """Route one popped run while the in-hand counter covers the gap
        until each group lands in an inbox (or finishes inline)."""
        key, groups = run
        if not ledger.is_on():
            for group in groups:
                self._route((key, group))
            return
        self._note_in_hand(len(groups))
        left = len(groups)
        try:
            for group in groups:
                self._route((key, group))
                self._note_in_hand(-1)
                left -= 1
        finally:
            if left:        # a raising route must not leave phantom in-hand
                self._note_in_hand(-left)

    def _route(self, item: Tuple[int, PipelineEventGroup]) -> None:
        key, group = item
        shard = self._shard(key, group)
        inbox = self._inboxes[shard]
        stalled = False
        # a full inbox blocks here — that is the back-pressure hop; the
        # timeout only exists so a wedged worker cannot wedge dispatch
        # (and with it the flush pump) forever.  Known tradeoff: while one
        # shard's inbox is full, dispatch (and with it every pipeline)
        # waits — the same agent-wide escalation the reference's
        # thread_count=1 default has, traded here for per-source ordering;
        # per-pipeline dispatch isolation is future work
        while not inbox.put(item, timeout=1.0):
            if inbox.is_closed():
                # forced shutdown (stop() closed the inboxes after the
                # drain-join timed out): process inline on this thread
                # rather than dropping — the old single-thread drain
                # semantics; ordering past this point is best-effort
                self._process_one(key, group)
                return
            if not stalled:
                # a worker whose full inbox blocked dispatch for a whole
                # timeout round is stalled — one flight event per episode
                # (no lock held here: the put timed out and returned)
                stalled = True
                flight.record("worker.stall", worker=shard,
                              depth=len(inbox))
            self._pump_timeout_flush()

    # -- workers ------------------------------------------------------------

    def _make_relief(self, lane: WorkerLane):
        """Budget-relief hook bound to ONE lane: when this worker waits for
        in-flight budget while dispatching, finish the overlapped group the
        lane holds so the bytes it owns are released.  Bound explicitly (not
        read from TLS at call time) so the hook always completes the owning
        worker's group even if relief plumbing ever runs off-thread."""
        def _relieve() -> bool:
            return self._step_oldest(lane) is not None
        return _relieve

    def _advance_ring(self, lane: WorkerLane) -> None:
        """loongstream ring discipline, shared by both loops: complete the
        OLDEST pending group when the ring is at capacity (the span-return
        stage of the pipeline: group N-depth+1 materialises while the
        device computes the newer ones) or when it outlived the
        auto-tuner's flush deadline (latency backstop for trickle
        traffic)."""
        while lane.full():
            ent = self._step_oldest(lane)
            if ent is None:
                break
            if ent.gone and ent.stage:
                # the staircase: the group that left had advanced through
                # ``stage`` device stages; the one behind it moves up one
                # now, so that its next stage has this turn in flight and
                # is not waited for the moment it was dispatched.  A chain
                # with one device stage never comes here.
                behind = lane.oldest_stage()
                if behind is not None and behind < ent.stage:
                    self._step_oldest(lane)
        age = lane.oldest_age()
        if age is not None and age > auto_tuner().flush_deadline_s():
            self._step_oldest(lane)

    def _run_single(self, worker_id: int) -> None:
        """thread_count == 1: the reference shape — pop the queue manager
        directly, no dispatch hop.  Pops are backlog-aware runs
        (loongcolumn): the per-pop/per-dispatch hand-off amortises over
        whatever occupancy the queue actually holds."""
        lane = self._lanes[worker_id]
        set_budget_relief(self._make_relief(lane))
        prof.push_marker("worker", f"processor-{worker_id}")
        had_item = False
        try:
            while self._running:
                self._pump_timeout_flush()
                # while device work is in flight, poll rather than sleep: an
                # empty queue means the overlap window closes and we complete
                run = self.pqm.pop_run(
                    timeout=0.0 if lane.busy() else 0.2,
                    max_groups=self.run_max_groups)
                if run is None:
                    had_item = False
                    self._step_oldest(lane)
                    continue
                if had_item or len(run[1]) > 1:
                    # sustained backlog on the single worker (consecutive
                    # non-empty pops, or a multi-group run): probe the
                    # device-idle accounting (the sharded loop probes on
                    # inbox depth instead)
                    note_host_backlog()
                had_item = True
                self._handle_run(run[0], run[1], lane)
            self._complete_lane(lane)
            # drain remaining items on stop
            while True:
                run = self.pqm.pop_run(timeout=0,
                                   max_groups=self.run_max_groups)
                if run is None:
                    break
                self._handle_run(run[0], run[1], None)
        finally:
            prof.pop_marker()
            set_budget_relief(None)

    def _chip_lane_for(self, worker_id: int):
        """loongmesh: this worker's home chip lane (source → worker →
        chip: the CRC32 affinity hash picked the worker, ``worker_id %
        n_chips`` picks the chip).  None when ≤1 device is attached or
        lane routing is off (``LOONG_MESH_LANES=0``) — dispatches then
        stay on the full-mesh / single-device path.  Fail-soft: a missing
        backend must never kill a worker thread."""
        try:
            return chip_lanes.router().lane_for_worker(worker_id)
        except Exception:  # noqa: BLE001
            log.exception("chip-lane routing unavailable; worker %d "
                          "stays unbound", worker_id)
            return None

    def chip_lane_map(self) -> List[Optional[int]]:
        """worker index -> bound chip index (None = unbound), for
        /debug/status and the affinity-determinism tests."""
        out: List[Optional[int]] = []
        for i in range(self.thread_count if self.thread_count > 1 else 0):
            lane = self._chip_lane_for(i)
            out.append(lane.index if lane is not None else None)
        return out

    def _run_worker(self, worker_id: int) -> None:
        """Sharded mode: consume this worker's inbox with the same
        overlapped device lane ring as the single-thread loop.  The
        worker binds to its home chip lane for the duration — every
        device dispatch it makes lands on that chip."""
        lane = self._lanes[worker_id]
        inbox = self._inboxes[worker_id]
        set_budget_relief(self._make_relief(lane))
        chip_lanes.set_thread_lane(self._chip_lane_for(worker_id))
        prof.push_marker("worker", f"processor-{worker_id}")
        try:
            while True:
                run = inbox.get_run(
                    timeout=0.0 if lane.busy() else 0.2,
                    max_groups=self.run_max_groups)
                if run is None:
                    self._step_oldest(lane)
                    if inbox.drained():
                        break
                    continue
                if len(inbox):
                    # host has backlog at this very moment: charge any
                    # device-idle gap (utilization accounting — the
                    # "shard more vs device-bound" counter)
                    note_host_backlog()
                self._handle_run(run[0], run[1], lane)
            self._complete_lane(lane)
        finally:
            prof.pop_marker()
            chip_lanes.set_thread_lane(None)
            set_budget_relief(None)

    def _handle_run(self, key: int, groups: List[PipelineEventGroup],
                    lane: Optional[WorkerLane]) -> None:
        """One popped run through dispatch → ring advance → lane, with
        the in-hand counter covering the whole hop (a group anchored in
        the lane ring or _in_process_cnt is visible to live_inflight;
        this covers the slivers in between).

        Dispatch is PER GROUP even though the pop was a run:
         * the lane ring + budget-relief protocol is per pending entry —
           a whole run inside ONE process_begin would let group N+1's
           device dispatch wait on budget held by group N's pending,
           which only materialises after the run returns (intra-run
           budget deadlock the relief hook cannot see);
         * sampled tracing draws one deterministic key per group
           ("pipeline:N") — a replayed storm must trace the identical
           population.
        The run amortises the HAND-OFF (one queue lock/CV cycle, one
        aggregated dequeue record, one inbox drain per run) — that, not
        chain batching, was the measured cost."""
        led = ledger.is_on()
        if led:
            self._note_in_hand(len(groups))
        try:
            for group in groups:
                if lane is None:
                    self._process_one(key, group)
                    continue
                nxt = self._dispatch_one(key, group, lane=lane)
                # dispatch-before-advance is the overlap: the device now
                # holds group N+1 while we materialise + send the oldest
                # ring entry (N-depth+1)
                self._advance_ring(lane)
                lane.put(nxt)
        finally:
            if led:
                self._note_in_hand(-len(groups))

    def _dispatch_one(self, key: int, group: PipelineEventGroup,
                      lane: Optional[WorkerLane] = None):
        """Host pre-processing + device dispatch for one group.  Returns
        a pending handle when device work stays in flight, else None
        (group fully processed and sent).

        Ordering invariant: when this group resolves on the host tier
        (finish is None) it is SENT here, inline — so the worker's lane
        must be completed first.  Otherwise a device-routed group N could
        still sit in the lane while host-routed group N+1 of the SAME
        source overtakes it at the sink (observed in the agent drive: the
        first group of a stream pays the XLA compile on the device path
        while later small groups take the native walker)."""
        pipeline = self.pipeline_manager.find_pipeline_by_queue_key(key)
        n_events = len(group)
        if pipeline is None:
            log.warning("no pipeline for queue key %d; dropping group", key)
            ack_watermark.ack_groups([group], force=True)
            if ledger.is_on() or slo.is_on():
                q = self.pqm.get_queue(key)
                # hot reload can delete the queue between pop and here:
                # attribute the drop via the manager's tombstone so the
                # ingesting pipeline's books still balance
                name = (q.pipeline_name if q is not None
                        else self.pqm.retired_pipeline_name(key))
                if ledger.is_on():
                    ledger.record(name, ledger.B_DROP, n_events,
                                  group.data_size(), tag="no_pipeline")
                if slo.is_on():
                    slo.observe_groups(name, [group], slo.OUTCOME_DROP)
            return None
        self.in_groups.add(1)
        self.in_events.add(n_events)
        self.in_bytes.add(group.data_size())
        groups = [group]
        t0 = time.perf_counter()
        sp = None
        tracer = trace.active_tracer()
        if tracer is not None:
            # deterministic per-group sampling: the Nth group of pipeline P
            # draws from (seed, "P:N") only — a replayed soak traces the
            # identical group set (docs/observability.md)
            gkey = tracer.next_group_key(pipeline.name or "pipeline")
            if tracer.should_sample(gkey):
                # a stopwatch: once its device work is in flight (or the
                # lane ring is drained under it) the thread's CPU goes to
                # other groups, so it takes no CPU reading of its own; the
                # stages under it take theirs
                sp = tracer.start_span(
                    "pipeline.process", trace_id=gkey,
                    attrs={"pipeline": pipeline.name, "events": n_events},
                    cpu=False)
                tracer.push_current(sp)
        prof.push_marker("pipeline", pipeline.name or "pipeline")
        # loongtenant: device dispatches made inside this chain walk count
        # against THIS pipeline's budget share (ops/device_plane).
        # Save/restore, not set/clear: the budget-relief hook completes a
        # lane group INSIDE another pipeline's submit wait on this same
        # thread — clearing would strip the outer dispatch's binding
        prev_tenant = current_tenant()
        set_thread_tenant(pipeline.name or None)
        try:
            try:
                finish = pipeline.process_begin(groups)
            except Exception:  # noqa: BLE001
                log.exception("pipeline %s processing failed", pipeline.name)
                self._ledger_error_drop(pipeline, groups)
                self._finish_group(sp, t0, "error")
                return None
            if finish is None:
                if lane is not None:
                    # drain the overlapped group BEFORE this inline send:
                    # same worker ⇒ possibly same source; send order = pop
                    # order
                    self._complete_lane(lane)
                self._send(pipeline, groups)
                self._finish_group(sp, t0, "ok")
                return None
        finally:
            set_thread_tenant(prev_tenant)
            prof.pop_marker()
        # the group's device work stays in flight: detach its span from
        # this thread so the NEXT group's dispatch does not nest under it
        if sp is not None:
            tracer.pop_current(sp)
        lane_tag = (f"lane{lane.worker_id}" if lane is not None else "inline")
        if ledger.is_on():
            ledger.record(pipeline.name, ledger.B_DEVICE_SUBMIT,
                          sum(len(g) for g in groups), tag=lane_tag)
        return pipeline, groups, finish, sp, t0, lane_tag

    def _ledger_error_drop(self, pipeline, groups) -> None:
        """A processing exception terminally discards the group's events:
        without this record the conservation residual would read the bug
        as a silent loss instead of an attributed drop."""
        ack_watermark.ack_groups(groups, force=True)
        if slo.is_on():
            slo.observe_groups(pipeline.name, groups, slo.OUTCOME_DROP)
        ledger.record(pipeline.name, ledger.B_DROP,
                      sum(len(g) for g in groups), tag="process_error")

    def _finish_group(self, sp, t0: float, status: str) -> None:
        self.e2e_hist.observe(time.perf_counter() - t0)
        if sp is not None:
            tracer = trace.active_tracer()
            if tracer is not None:
                tracer.pop_current(sp)
            sp.end(status)

    def _step_oldest(self, lane: WorkerLane):
        """Advance the lane ring one step: the oldest pending group's
        stage in flight materialises and its chain walks on — to the send
        (it leaves the ring) or to its next device stage (it keeps its
        place).  Returns the entry stepped (``gone`` says which), None when
        the ring had nothing to step."""
        got = lane.check_out()
        if got is None:
            return None
        ent, hold_send = got
        kept = None
        try:
            kept = self._complete(ent.pending, hold_send)
        finally:
            lane.check_in(ent, kept)
        return ent

    def _complete_lane(self, lane: WorkerLane) -> None:
        """Drain the WHOLE lane ring in FIFO order — required before any
        inline (host-tier) send of a possibly-same-source group, and on
        worker exit."""
        while self._step_oldest(lane) is not None:
            pass

    def _complete(self, pending, hold_send: bool = False):
        """One step of a pending group.  Returns the pending that keeps its
        place in the ring — with the next stage's continuation, or parked
        (continuation None) when the chain is done but ``hold_send`` says
        an older group has yet to be sent — or None once the group has
        been sent (or dropped on an error)."""
        pipeline, groups, finish, sp, t0, lane_tag = pending
        tracer = trace.active_tracer()
        if sp is not None and tracer is not None:
            # re-attach: device materialisation + downstream processors +
            # send events belong to this group's span
            tracer.push_current(sp)
        prof.push_marker("pipeline", pipeline.name or "pipeline")
        # in-hand across the whole completion: the lane entry was already
        # take()n and finish()'s exit drops _in_process_cnt BEFORE the
        # send — without this, a sink write stalling mid-_send (NFS,
        # loaded CI) leaves the group in no occupancy counter and a
        # stable ledger, faking a quiesce into a false residual alarm
        led = ledger.is_on()
        if led:
            self._note_in_hand(1)
        # completion may re-dispatch (fused demotion re-runs, drain hops):
        # those submits bill this pipeline's tenant share too.  _complete
        # runs from the budget-relief hook inside ANOTHER pipeline's
        # submit wait, so restore rather than clear
        prev_tenant = current_tenant()
        set_thread_tenant(pipeline.name or None)
        try:
            if finish is not None:
                try:
                    # a continuation hands back the next one; anything
                    # else it returns means the chain is done
                    nxt = finish()
                    finish = nxt if callable(nxt) else None
                except Exception:  # noqa: BLE001
                    log.exception("pipeline %s processing failed",
                                  pipeline.name)
                    self._ledger_error_drop(pipeline, groups)
                    self._finish_group(sp, t0, "error")
                    return None
            if finish is not None or hold_send:
                # still in the ring: detach its span from this thread, as
                # _dispatch_one does, so the next group does not nest
                if sp is not None and tracer is not None:
                    tracer.pop_current(sp)
                return pipeline, groups, finish, sp, t0, lane_tag
            if ledger.is_on():
                # device work resolved: the group's spans are host-resident
                # again — the submit→materialize gap is the ring occupancy
                ledger.record(pipeline.name, ledger.B_DEVICE_MATERIALIZE,
                              sum(len(g) for g in groups), tag=lane_tag)
            self._send(pipeline, groups)
            self._finish_group(sp, t0, "ok")
            return None
        finally:
            if led:
                self._note_in_hand(-1)
            set_thread_tenant(prev_tenant)
            prof.pop_marker()

    def _send(self, pipeline, groups) -> None:
        try:
            pipeline.send(groups)
        except Exception:  # noqa: BLE001
            log.exception("pipeline %s send failed", pipeline.name)
            # best-effort terminal record: send() may have routed part of
            # the batch before raising, so a nonzero (negative) residual
            # here is the auditor doing its job on a genuine bug path
            ledger.record(pipeline.name, ledger.B_DROP,
                          sum(len(g) for g in groups), tag="send_error")

    def _process_one(self, key: int, group: PipelineEventGroup) -> None:
        pending = self._dispatch_one(key, group)
        while pending is not None:
            pending = self._complete(pending)
