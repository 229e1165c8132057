"""flusher_file — local file sink (reference
core/plugin/flusher/file/FlusherFile.cpp: spdlog-based JSON sink, written
by the logger's own thread behind a queue that blocks when full)."""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Dict, List

from .. import native, trace
from ..models import PipelineEventGroup
from ..pipeline.batch.batcher import Batcher
from ..pipeline.batch.flush_strategy import FlushStrategy
from ..pipeline.plugin.interface import Flusher, PluginContext
from ..pipeline.serializer.json_serializer import JsonSerializer
from .flush_sender import FlushSender


class FlusherFile(Flusher):
    name = "flusher_file"
    supports_columnar = True
    # loongledger: NOT ledger_terminal — send() only stages into the
    # batcher, whose flush only hands the batch to the sender thread (the
    # auditor counts both stations' occupancy); the terminal record lands
    # in _flush_groups AFTER the write, so a failed write is a visible
    # drop, never a pre-booked send_ok, and a batch that waits for its
    # write is not acknowledged

    def __init__(self) -> None:
        super().__init__()
        self.file_path = ""
        self.serializer = JsonSerializer()
        self.batcher: Batcher = None  # type: ignore
        self._sender = FlushSender(self._flush_groups, self.name)

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        super().init(config, context)
        self.file_path = config.get("FilePath", "")
        if not self.file_path:
            return False
        d = os.path.dirname(self.file_path)
        if d:
            os.makedirs(d, exist_ok=True)
        strategy = FlushStrategy(
            min_cnt=int(config.get("MinCnt", 0)),
            min_size_bytes=int(config.get("MinSizeBytes", 256 * 1024)),
            timeout_secs=float(config.get("TimeoutSecs", 1.0)))
        self.batcher = Batcher(strategy, on_flush=self._enqueue,
                               flusher_id=self.name,
                               pipeline_name=context.pipeline_name)
        return True

    def send(self, group: PipelineEventGroup) -> bool:
        self.batcher.add(group)
        return True

    def _enqueue(self, groups: List[PipelineEventGroup]) -> None:
        """The batcher's flush, on the worker or the timeout thread: the
        batch as it is goes to the sender's FIFO (`flusher.enqueue`, a
        child of `flusher.send` on the worker — its length is the
        backpressure of a sender that cannot keep up)."""
        n_events = sum(len(g) for g in groups)
        tracer = trace.active_tracer()
        sp = (tracer.child_or_sampled(
            "flusher", "flusher.enqueue",
            {"flusher": self.name, "groups": len(groups),
             "events": n_events}) if tracer is not None else None)
        with sp or contextlib.nullcontext():
            self._sender.put(groups, n_events)

    def _flush_groups(self, groups: List[PipelineEventGroup]) -> None:
        """One batch, one serialize, one open-write-close, then the
        terminal accounting — on the sender thread, which alone writes.
        Few calls that let go of the interpreter lock, not few operations:
        after each the sender asks for the lock again, and every stretch
        it then holds it is one the worker may have to wait out (a sender
        that made eight such calls a batch cost the worker as much as the
        flush it took off it: PERF.md section 6, PR 30).  So a batch of
        one columnar group — the 512 KiB group that is a batch of its own
        — is assembled and appended in one native call; any other batch
        is serialized without a second copy of the payload and written in
        one native call."""
        def run() -> None:
            if len(groups) != 1 or not self._append_one(groups[0]):
                self._serialize_then_write(
                    groups, self.serializer.serialize_view, self._write)
        self._ledger_terminal_write(groups, run)

    def _append_one(self, group: PipelineEventGroup) -> bool:
        tracer = trace.active_tracer()
        if tracer is not None:
            t0 = time.perf_counter()
            cpu0 = time.thread_time()
        done = self.serializer.append_group(group, self.file_path)
        if done is None:
            return False
        if tracer is not None:
            # the two halves as the native call timed them, under the
            # names and attributes _serialize_then_write gives them.  The
            # call is one stretch of this thread's CPU clock: the pair's
            # CPU seconds are on flusher.serialize, flusher.write has None
            cpu_s = time.thread_time() - cpu0
            nbytes, serialize_s, write_s = done
            attrs = {"flusher": self.name, "groups": 1, "events": len(group),
                     "nbytes": nbytes}
            tracer.record_timed("flusher", "flusher.serialize", t0,
                                serialize_s, attrs, cpu_s)
            tracer.record_timed("flusher", "flusher.write",
                                t0 + serialize_s, write_s, attrs)
        return True

    def _write(self, data) -> None:
        if not native.append_file(self.file_path, data):
            with open(self.file_path, "ab") as f:
                f.write(data)

    def inflight_events(self) -> int:
        """Events handed to the sender and not yet written and accounted
        (the ledger auditor's occupancy probe, monitor/ledger.py)."""
        return self._sender.inflight_events()

    def flush_status(self) -> Dict[str, float]:
        """This sink's entry of /debug/status ``flush``."""
        return self._sender.status()

    def flush_all(self) -> bool:
        self.batcher.flush_all()
        self._sender.drain()
        return True

    def stop(self, is_pipeline_removing: bool = False) -> bool:
        self.batcher.flush_all()
        self.batcher.close()
        self._sender.stop()
        return True
