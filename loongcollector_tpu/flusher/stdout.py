"""flusher_stdout — JSON lines to stdout (quick-start sink; the reference's
quick-start uses flusher_stdout from the Go runtime — here it's native)."""

from __future__ import annotations

import sys
from typing import Any, Dict, List

from ..models import PipelineEventGroup
from ..pipeline.batch.batcher import Batcher
from ..pipeline.batch.flush_strategy import FlushStrategy
from ..pipeline.plugin.interface import Flusher, PluginContext
from ..pipeline.serializer.json_serializer import JsonSerializer


class FlusherStdout(Flusher):
    name = "flusher_stdout"
    supports_columnar = True
    # loongledger: NOT ledger_terminal — send() stages into the batcher;
    # the terminal record lands in _flush_groups after the stream write
    # (see FlusherFile for the rationale)

    def __init__(self) -> None:
        super().__init__()
        self.serializer = JsonSerializer()
        self.batcher: Batcher = None  # type: ignore
        self.only_stdout = True
        self._stream = sys.stdout

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        super().init(config, context)
        # stdout flushes immediately by default (interactive quick start)
        strategy = FlushStrategy(min_cnt=int(config.get("MinCnt", 0)) or 1,
                                 min_size_bytes=0, timeout_secs=1.0)
        self.batcher = Batcher(strategy, on_flush=self._flush_groups,
                               flusher_id=self.name,
                               pipeline_name=context.pipeline_name)
        return True

    def send(self, group: PipelineEventGroup) -> bool:
        self.batcher.add(group)
        return True

    def _flush_groups(self, groups: List[PipelineEventGroup]) -> None:
        def write(data: bytes) -> None:
            self._stream.write(data.decode("utf-8", "replace"))
            self._stream.flush()
        self._serialize_and_write(groups, self.serializer.serialize, write)

    def flush_all(self) -> bool:
        self.batcher.flush_all()
        return True

    def stop(self, is_pipeline_removing: bool = False) -> bool:
        self.batcher.flush_all()
        self.batcher.close()
        return True
