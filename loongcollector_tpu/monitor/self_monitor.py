"""Self-monitor server: agent metrics/alarms re-ingested as pipelines.

Reference: core/monitor/SelfMonitorServer.cpp:129,224,328 — a thread converts
metric records and alarms into event groups and pushes them into INTERNAL
collection pipelines consumed by input_internal_metrics /
input_internal_alarms (dogfooding: the agent observes itself through its own
data plane).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from .. import trace
from ..models import PipelineEventGroup
from ..utils.logger import get_logger
from .alarms import AlarmManager
from .metrics import ReadMetrics

log = get_logger("self_monitor")

SEND_INTERVAL_S = 60.0


class SelfMonitorServer:
    _instance: Optional["SelfMonitorServer"] = None
    _instance_lock = threading.Lock()

    def __init__(self) -> None:
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._lock = threading.Lock()
        # queue keys of the internal pipelines (set by the internal inputs)
        self._metrics_queue_key: Optional[int] = None
        self._alarms_queue_key: Optional[int] = None
        self._traces_queue_key: Optional[int] = None
        self.process_queue_manager = None
        self.interval_s = SEND_INTERVAL_S

    @classmethod
    def instance(cls) -> "SelfMonitorServer":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    # -- registration by internal input plugins -----------------------------

    def set_metrics_pipeline(self, queue_key: Optional[int]) -> None:
        with self._lock:
            self._metrics_queue_key = queue_key

    def set_alarms_pipeline(self, queue_key: Optional[int]) -> None:
        with self._lock:
            self._alarms_queue_key = queue_key

    def set_traces_pipeline(self, queue_key: Optional[int]) -> None:
        """Route loongtrace spans/events to their own internal pipeline;
        when unset they ride the metrics pipeline (dogfooding either way)."""
        with self._lock:
            self._traces_queue_key = queue_key

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        with self._lock:
            if self._running:
                return
            self._running = True
        self._thread = threading.Thread(target=self._run, name="self-monitor",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        with self._lock:
            if not self._running:
                return
            self._running = False
        if self._thread:
            self._thread.join(timeout=2)
            self._thread = None

    def _run(self) -> None:
        last = time.monotonic()
        while self._running:
            time.sleep(0.5)
            if time.monotonic() - last < self.interval_s:
                continue
            last = time.monotonic()
            try:
                with trace.span("self_monitor.tick"):
                    self.send_once()
            except Exception:  # noqa: BLE001
                log.exception("self monitor send failed")

    # -- conversion ----------------------------------------------------------

    def send_once(self) -> None:
        pqm = self.process_queue_manager
        if pqm is None:
            return
        from .runtime_stats import refresh
        refresh()   # pull device-plane / scraper / eBPF gauges
        with self._lock:
            mkey, akey = self._metrics_queue_key, self._alarms_queue_key
            tkey = self._traces_queue_key
        # check queue validity BEFORE draining counters/alarms: the drain is
        # destructive, and the window where the queue is full is exactly the
        # window whose telemetry must not be lost — deltas keep accumulating
        # until the queue reopens.
        if mkey is not None and pqm.is_valid_to_push(mkey):
            group = self._metrics_group()
            if group is not None and not group.empty():
                pqm.push_queue(mkey, group)
        if akey is not None and pqm.is_valid_to_push(akey):
            group = self._alarms_group()
            if group is not None and not group.empty():
                pqm.push_queue(akey, group)
        # traces share the metrics pipeline unless routed to their own;
        # same destructive-drain rule: only drain into a pushable queue
        tkey = tkey if tkey is not None else mkey
        if tkey is not None and pqm.is_valid_to_push(tkey):
            group = self._traces_group()
            if group is not None and not group.empty():
                pqm.push_queue(tkey, group)

    @staticmethod
    def _metrics_group() -> Optional[PipelineEventGroup]:
        snaps = ReadMetrics.snapshot(reset_counters=True)
        if not snaps:
            return None
        group = PipelineEventGroup()
        sb = group.source_buffer
        now = int(time.time())
        for snap in snaps:
            ev = group.add_metric_event(now)
            ev.set_name(sb.copy_string(snap["category"]))
            values = {}
            for k, v in snap["counters"].items():
                values[k] = float(v)
            for k, v in snap["gauges"].items():
                values[k] = float(v)
            for k, h in snap.get("histograms", {}).items():
                # flattened percentile trio + volume: the self-monitor
                # stream is multi-value metric events, not bucket vectors
                # (the exposition endpoint serves the full buckets)
                values[f"{k}_count"] = float(h["count"])
                values[f"{k}_p50"] = float(h["p50"])
                values[f"{k}_p90"] = float(h["p90"])
                values[f"{k}_p99"] = float(h["p99"])
                values[f"{k}_max"] = float(h["max"])
            if values:
                ev.set_multi_value(values)
            for k, v in snap["labels"].items():
                ev.set_tag(sb.copy_string(k), sb.copy_string(str(v)))
        group.set_tag(b"__source__", b"self_monitor")
        return group

    @staticmethod
    def _traces_group() -> Optional[PipelineEventGroup]:
        """Drain the active tracer into one event group (spans + timeline
        events as log events, __source__ = loongtrace).  No-op when
        tracing is disabled — the drain is destructive, so it only runs
        against a live tracer."""
        from .. import trace
        tracer = trace.active_tracer()
        if tracer is None:
            return None
        spans, events = tracer.drain()
        if not spans and not events:
            return None
        from ..trace.export import traces_to_group
        return traces_to_group(spans, events)

    @staticmethod
    def _alarms_group() -> Optional[PipelineEventGroup]:
        alarms = AlarmManager.instance().flush()
        if not alarms:
            return None
        group = PipelineEventGroup()
        sb = group.source_buffer
        now = int(time.time())
        for alarm in alarms:
            ev = group.add_log_event(now)
            for k, v in alarm.items():
                ev.set_content(sb.copy_string(k), sb.copy_string(v))
        group.set_tag(b"__source__", b"self_monitor")
        return group
