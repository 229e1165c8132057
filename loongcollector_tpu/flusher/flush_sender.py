"""A write-through sink's own sender thread, behind a bounded FIFO.

Reference: core/plugin/flusher/file/FlusherFile.cpp hands each serialized
batch to an asynchronous spdlog logger whose queue blocks when full — the
processor thread never writes.  This is that shape for a flusher whose
flush is serialize → local write → terminal accounting (flusher_file):

  batcher flush → put() → FIFO of at most FIFO_BATCHES → sender thread →
  flush_fn(groups)                       (one batch, one flush, in order)

Not the AsyncSinkFlusher family (flusher/async_sink.py): no retry, TTL,
breaker or spill.  A local write either lands or fails now, a full FIFO
blocks the caller as the write itself used to, and nothing ages out.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple

from ..models import PipelineEventGroup
from ..utils.logger import get_logger

#: batches the FIFO holds, the one being written included.  Each pins its
#: groups' arenas (some 0.6 MB at the file sink's default batch), so this
#: is the memory bound too.
FIFO_BATCHES = 6


class FlushSender:
    """Runs ``flush_fn(groups)`` for every batch handed to `put`, one at a
    time and in hand-over order, on a thread of its own.  `flush_fn` owns
    the batch's fate (Flusher._ledger_terminal_write records a failed
    write as a terminal drop and does not raise).

    A batch stays in the FIFO — counted by `inflight_events`, holding its
    place — until its flush has returned: the events of a batch that waits
    or is mid-write are in flight, never in no counter."""

    def __init__(self, flush_fn: Callable[[List[PipelineEventGroup]], None],
                 name: str) -> None:
        self._flush = flush_fn
        self._name = name
        self._fifo: Deque[Tuple[List[PipelineEventGroup], int]] = \
            collections.deque()
        # one condition for the three waits: the idle sender, a caller at
        # a full FIFO, a barrier
        self._cv = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self._events = 0
        self._batches_total = 0
        self._offloaded_total = 0
        self._enqueue_blocked_total = 0
        self._enqueue_blocked_seconds = 0.0
        self._depth_max = 0

    def put(self, groups: List[PipelineEventGroup], n_events: int) -> None:
        """Append one batch; blocks while the FIFO is full."""
        with self._cv:
            if len(self._fifo) >= FIFO_BATCHES:
                self._enqueue_blocked_total += 1
                t0 = time.perf_counter()
                while len(self._fifo) >= FIFO_BATCHES:
                    self._cv.wait()
                self._enqueue_blocked_seconds += time.perf_counter() - t0
            self._fifo.append((groups, n_events))
            self._events += n_events
            self._batches_total += 1
            self._depth_max = max(self._depth_max, len(self._fifo))
            if self._thread is None:
                self._stopping = False
                self._thread = threading.Thread(
                    target=self._run, name=f"{self._name}-sender",
                    daemon=True)
                self._thread.start()
            self._cv.notify_all()

    def _run(self) -> None:
        fifo = self._fifo
        written = False
        while True:
            with self._cv:
                if written:
                    # the flush has returned: only now does the batch give
                    # up its place and its events
                    self._events -= fifo.popleft()[1]
                    self._offloaded_total += 1
                    self._cv.notify_all()
                while not fifo:
                    if self._stopping:
                        self._thread = None
                        return
                    self._cv.wait()
                groups = fifo[0][0]
            try:
                self._flush(groups)
            except Exception:  # noqa: BLE001
                # the thread must outlive a flush_fn that breaks its word:
                # a dead sender is a pipeline blocked at a full FIFO
                get_logger("flusher").exception(
                    "%s sender: flush raised", self._name)
            written = True
            del groups      # an idle sender pins no batch

    def drain(self) -> None:
        """Return once every batch handed over so far is flushed."""
        with self._cv:
            target = self._batches_total
            while self._offloaded_total < target:
                self._cv.wait()

    def stop(self) -> None:
        """End the thread once the FIFO is empty: a barrier like `drain`.
        A later `put` starts a new thread."""
        with self._cv:
            thread = self._thread
            self._stopping = True
            self._cv.notify_all()
        if thread is not None:
            thread.join()

    def inflight_events(self) -> int:
        """Events of the batches that wait or are being written."""
        with self._cv:
            return self._events

    def status(self) -> Dict[str, float]:
        """The /debug/status ``flush`` entry of this sender."""
        with self._cv:
            return {
                "batches_total": self._batches_total,
                "offloaded_total": self._offloaded_total,
                "enqueue_blocked_total": self._enqueue_blocked_total,
                "enqueue_blocked_seconds": round(
                    self._enqueue_blocked_seconds, 6),
                "depth": len(self._fifo),
                "depth_max": self._depth_max,
            }
