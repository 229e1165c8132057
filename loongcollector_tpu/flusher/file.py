"""flusher_file — local file sink (reference
core/plugin/flusher/file/FlusherFile.cpp: spdlog-based JSON sink)."""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List

from ..models import PipelineEventGroup
from ..pipeline.batch.batcher import Batcher
from ..pipeline.batch.flush_strategy import FlushStrategy
from ..pipeline.plugin.interface import Flusher, PluginContext
from ..pipeline.serializer.json_serializer import JsonSerializer


class FlusherFile(Flusher):
    name = "flusher_file"
    supports_columnar = True
    # loongledger: NOT ledger_terminal — send() only stages into the
    # batcher (whose occupancy the auditor counts); the terminal record
    # lands in _flush_groups AFTER the write, so a failed write is a
    # visible drop, never a pre-booked send_ok

    def __init__(self) -> None:
        super().__init__()
        self.file_path = ""
        self.serializer = JsonSerializer()
        self.batcher: Batcher = None  # type: ignore
        self._lock = threading.Lock()

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
        self.batcher = Batcher(strategy, on_flush=self._flush_groups,
                               flusher_id=self.name,
                               pipeline_name=context.pipeline_name)
        return True

    def send(self, group: PipelineEventGroup) -> bool:
        self.batcher.add(group)
        return True

    def _flush_groups(self, groups: List[PipelineEventGroup]) -> None:
        def write(data: bytes) -> None:
            with self._lock:
                with open(self.file_path, "ab") as f:
                    f.write(data)
        self._serialize_and_write(groups, self.serializer.serialize, write)

    def flush_all(self) -> bool:
        self.batcher.flush_all()
        return True

    def stop(self, is_pipeline_removing: bool = False) -> bool:
        self.batcher.flush_all()
        self.batcher.close()
        return True
