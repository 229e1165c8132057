"""Bounded process queues with high/low watermark back-pressure.

Reference: core/collection_pipeline/queue/BoundedProcessQueue.cpp:34,53,89-93
and QueueParam.h:23-33 (high watermark = capacity, low = cap*2/3 by default).
Push fails above the high watermark; popping below the low watermark fires the
upstream FeedbackInterface so blocked inputs resume — the same contract the
TPU device queue honours (SURVEY.md §5.8: the host↔device boundary lives
behind these watermarks).

CircularProcessQueue (drop-oldest) serves streaming inputs that must never
block the producer (eBPF perf buffers, Prometheus streams — reference
queue/CircularProcessQueue.cpp).
"""

from __future__ import annotations

import enum
import threading
import time
from collections import deque
from typing import Callable, Deque, List, Optional, Tuple

from ... import chaos
from ...models import PipelineEventGroup
from ...monitor import ledger

DEFAULT_CAPACITY = 20
LOW_WATERMARK_RATIO = 2 / 3

# loongcolumn backlog-aware hand-off: the queue is bounded in BYTES as well
# as groups.  A count-only bound lets large groups (512 KB reader chunks)
# pile up ~15 MB of backlog, and at a few ms service time per group that IS
# the 131 ms queue_wait plateau an early run recorded — every group waited
# capacity x service_time regardless of load.  The byte watermark keeps the
# standing backlog shallow (the producer feedback-blocks earlier), so
# queue_wait tracks the actual service rate; the count bound still guards
# the many-tiny-groups shape.  0 disables the byte bound.
DEFAULT_MAX_BYTES = 2 * 1024 * 1024

FP_PUSH = chaos.register_point("bounded_queue.push")

# shared queue-wait histogram (lazy: importing queues never touches the
# metrics registry); every bounded process queue observes into it
_wait_hist = None


def queue_wait_histogram():
    global _wait_hist
    if _wait_hist is None:
        from ...monitor.metrics import shared_histogram
        _wait_hist = shared_histogram("queue_wait_seconds",
                                      labels={"component": "process_queue"})
    return _wait_hist


class QueueStatus(enum.Enum):
    OK = 0
    FULL = 1
    EMPTY = 2


class FeedbackInterface:
    """Upstream wakeup hook (reference queue/FeedbackInterface.h)."""

    def feedback(self, key: int) -> None:  # pragma: no cover - interface
        raise NotImplementedError


class BoundedProcessQueue:
    """Count-bounded MPSC queue with watermark feedback.

    Thread-safe; producers are input threads, the consumer is a processor
    runner.  `set_pop_enabled(False)` supports the drain-before-stop pipeline
    swap semantics (reference CollectionPipeline.cpp:659-677).
    """

    def __init__(self, key: int, priority: int = 1,
                 capacity: int = DEFAULT_CAPACITY,
                 pipeline_name: str = "",
                 max_bytes: int = DEFAULT_MAX_BYTES):
        self.key = key
        self.priority = priority
        self.pipeline_name = pipeline_name
        self._cap_high = max(capacity, 1)
        self._cap_low = max(int(capacity * LOW_WATERMARK_RATIO), 1)
        self._bytes_high = max(int(max_bytes), 0)       # 0 = unbounded
        self._bytes_low = int(self._bytes_high * LOW_WATERMARK_RATIO)
        self._bytes = 0
        self._items: Deque[PipelineEventGroup] = deque()
        # enqueue timestamps + sizes ride parallel FIFOs (groups use
        # __slots__, so neither can be stamped on the group itself)
        self._enq_ts: Deque[float] = deque()
        self._sizes: Deque[int] = deque()
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._valid_to_push = True
        self._pop_enabled = True
        self._retired = False
        self._feedback: List[FeedbackInterface] = []
        # metrics
        self.total_pushed = 0
        self.total_popped = 0
        self.total_rejected = 0

    # -- producer side ------------------------------------------------------

    def _over_high(self) -> bool:
        """High-watermark predicate (lock held): groups OR bytes."""
        if len(self._items) >= self._cap_high:
            return True
        return bool(self._bytes_high) and self._bytes >= self._bytes_high

    def _under_low(self) -> bool:
        """Low-watermark predicate (lock held): both bounds must clear
        before the upstream feedback fires."""
        if len(self._items) > self._cap_low:
            return False
        return not self._bytes_high or self._bytes <= self._bytes_low

    def push(self, group: PipelineEventGroup) -> bool:
        # an exception cannot propagate to input threads, so an injected
        # "error" degrades in this queue's own vocabulary: a watermark-style
        # rejection the producer already handles with feedback-blocking
        decision = chaos.faultpoint(FP_PUSH, raise_=False)
        if decision is not None and decision.action == chaos.ACTION_ERROR:
            with self._lock:
                self.total_rejected += 1
            return False
        # computed outside the lock, and ONLY when someone consumes it
        # (byte watermark or ledger): data_size() is O(events) on
        # materialized row groups
        size = group.data_size() if (self._bytes_high or ledger.is_on()) \
            else 0
        with self._lock:
            if self._retired or not self._valid_to_push:
                self.total_rejected += 1
                return False
            self._items.append(group)
            self._enq_ts.append(time.perf_counter())
            self._sizes.append(size)
            self._bytes += size
            self.total_pushed += 1
            if self._over_high():
                self._valid_to_push = False
            self._not_empty.notify()
        # loongledger: queue admit == enqueue boundary (outside the lock —
        # the ledger takes its own short lock)
        if ledger.is_on():
            ledger.record(self.pipeline_name, ledger.B_ENQUEUE,
                          len(group), size)
        return True

    def is_valid_to_push(self) -> bool:
        with self._lock:
            return self._valid_to_push

    # -- consumer side ------------------------------------------------------

    def _pop_locked(self) -> Tuple[PipelineEventGroup, Optional[float]]:
        """One popleft with its byte/timestamp bookkeeping (lock held)."""
        item = self._items.popleft()
        enq = self._enq_ts.popleft() if self._enq_ts else None
        if self._sizes:
            self._bytes -= self._sizes.popleft()
        self.total_popped += 1
        return item, enq

    def pop(self) -> Optional[PipelineEventGroup]:
        with self._lock:
            if not self._pop_enabled or not self._items:
                return None
            item, enq = self._pop_locked()
            if not self._valid_to_push and self._under_low():
                self._valid_to_push = True
                feedbacks = list(self._feedback)
            else:
                feedbacks = []
        if enq is not None:
            queue_wait_histogram().observe(time.perf_counter() - enq)
        if ledger.is_on():
            ledger.record(self.pipeline_name, ledger.B_DEQUEUE,
                          len(item), item.data_size())
        for fb in feedbacks:
            fb.feedback(self.key)
        return item

    def pop_run(self, max_groups: int, max_bytes: int
                ) -> List[PipelineEventGroup]:
        """Backlog-aware pop (loongcolumn): drain up to ``max_groups`` /
        ``max_bytes`` of queued groups in ONE lock acquisition.  The run
        length follows occupancy — a trickle pops one group exactly like
        pop(), a backlog amortises the per-pop hand-off (lock, CV, ledger,
        dispatch) across the whole run.  Per-group queue_wait attribution
        is preserved."""
        now = None
        waits: List[float] = []
        out: List[PipelineEventGroup] = []
        nbytes = 0
        with self._lock:
            if not self._pop_enabled:
                return out
            while self._items and len(out) < max_groups:
                if out and nbytes + (self._sizes[0] if self._sizes else 0) \
                        > max_bytes:
                    break
                size = self._sizes[0] if self._sizes else 0
                item, enq = self._pop_locked()
                nbytes += size
                out.append(item)
                if enq is not None:
                    if now is None:
                        now = time.perf_counter()
                    waits.append(now - enq)
            if out and not self._valid_to_push and self._under_low():
                self._valid_to_push = True
                feedbacks = list(self._feedback)
            else:
                feedbacks = []
        if waits:
            hist = queue_wait_histogram()
            for w in waits:
                hist.observe(w)
        if out and ledger.is_on():
            ledger.record(self.pipeline_name, ledger.B_DEQUEUE,
                          sum(len(g) for g in out), nbytes)
        for fb in feedbacks:
            fb.feedback(self.key)
        return out

    def oldest_age(self) -> Optional[float]:
        """Seconds the oldest queued group has waited (None when empty) —
        the per-pipeline ``queue_lag_seconds`` watermark (loongledger)."""
        with self._lock:
            if not self._enq_ts:
                return None
            return time.perf_counter() - self._enq_ts[0]

    def set_pop_enabled(self, enabled: bool) -> None:
        with self._lock:
            self._pop_enabled = enabled

    def retire(self) -> None:
        """Deleted-queue gate (loongledger): refuse new pushes and stop
        pops, under the same lock both check, so delete_queue's terminal
        accounting of the remaining groups is the last word — a racing
        push rolls back unledgered, a racing pop cannot re-terminate a
        group already counted dead."""
        with self._lock:
            self._retired = True
            self._pop_enabled = False

    def empty(self) -> bool:
        with self._lock:
            return not self._items

    def size(self) -> int:
        with self._lock:
            return len(self._items)

    def bytes_queued(self) -> int:
        with self._lock:
            return self._bytes

    def set_feedback(self, *feedbacks: FeedbackInterface) -> None:
        with self._lock:
            self._feedback = list(feedbacks)


class CircularProcessQueue(BoundedProcessQueue):
    """Drop-oldest variant: push never fails; over capacity the oldest group
    is discarded (reference queue/CircularProcessQueue.cpp)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.total_dropped = 0

    def push(self, group: PipelineEventGroup) -> bool:
        evicted = []
        size = group.data_size() if (self._bytes_high or ledger.is_on()) \
            else 0
        with self._lock:
            if self._retired:      # deleted queue: roll back, unledgered
                return False
            self._items.append(group)
            self._enq_ts.append(time.perf_counter())
            self._sizes.append(size)
            self._bytes += size
            self.total_pushed += 1
            # drop-oldest on EITHER bound: circular queues never block the
            # producer, so the byte watermark evicts instead of refusing
            # (len > 1 guard: one oversized group must still ship)
            while len(self._items) > self._cap_high or (
                    self._bytes_high and self._bytes > self._bytes_high
                    and len(self._items) > 1):
                evicted.append(self._items.popleft())
                if self._enq_ts:
                    self._enq_ts.popleft()
                if self._sizes:
                    self._bytes -= self._sizes.popleft()
                self.total_dropped += 1
            self._not_empty.notify()
        if ledger.is_on():
            ledger.record(self.pipeline_name, ledger.B_ENQUEUE,
                          len(group), size)
            # drop-oldest shedding is a terminal discard: ledgered with a
            # reason so the conservation residual stays zero by design
            for old in evicted:
                ledger.record(self.pipeline_name, ledger.B_DROP,
                              len(old), old.data_size(), tag="circular_evict")
        return True

    def is_valid_to_push(self) -> bool:
        return True
