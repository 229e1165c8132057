"""Plugin instance wrappers: lifecycle + per-instance metrics.

Reference: core/collection_pipeline/plugin/instance/ — ProcessorInstance
times each Process call and counts in/out events; FlusherInstance and
InputInstance wrap lifecycle.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

from ... import prof, trace
from ...models import PipelineEventGroup, columnar_enabled
from ...monitor import ledger, slo
from ...monitor.metrics import MetricsRecord
from ...runner import ack_watermark
from .interface import Flusher, Input, PluginContext, Processor


class ProcessorInstance:
    def __init__(self, plugin: Processor, plugin_id: str = ""):
        self.plugin = plugin
        self.plugin_id = plugin_id
        # loongcolumn: columnar groups pass through capable plugins
        # unmaterialized; everything else pays the (counted) expansion at
        # ITS boundary — never implicitly mid-plugin
        self.columnar_capable = bool(getattr(plugin, "supports_columnar",
                                             False))
        self._pipeline_name = ""
        self.metrics = MetricsRecord(
            category="plugin",
            labels={"plugin_type": plugin.name, "plugin_id": plugin_id})
        self.in_events = self.metrics.counter("in_events_total")
        self.out_events = self.metrics.counter("out_events_total")
        self.in_bytes = self.metrics.counter("in_size_bytes")
        self.cost_ms = self.metrics.counter("total_process_time_ms")
        # per-stage latency distribution (the ParPaRaw per-stage balance
        # view); the async device stage observes dispatch and complete
        # phases separately.  A stage's span is current on its thread for
        # its body (start_stage) and popped when it ends in the finally,
        # so the device legs and the flush spans nest under it
        self.stage_hist = self.metrics.histogram("stage_seconds")

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        self.plugin.metrics_record = self.metrics
        self._pipeline_name = getattr(context, "pipeline_name", "") or ""
        return self.plugin.init(config, context)

    def _ledger_delta(self, n_in: int, groups: List[PipelineEventGroup]
                      ) -> None:
        """loongledger: a stage that changed the event population either
        minted events (split: process_expand) or retired/held them
        (filter, multiline carry: process_drop), attributed to this
        plugin.  Runs from the stage's finally so a raising stage still
        balances against whatever it left in the groups."""
        delta = sum(len(g) for g in groups) - n_in
        if delta > 0:
            ledger.record(self._pipeline_name, ledger.B_PROCESS_EXPAND,
                          delta, tag=self.plugin_id or self.plugin.name)
        elif delta < 0:
            ledger.record(self._pipeline_name, ledger.B_PROCESS_DROP,
                          -delta, tag=self.plugin_id or self.plugin.name)

    def _materialize_boundary(self, groups: List[PipelineEventGroup]) -> None:
        """The lazy materialization boundary (loongcolumn): a plugin that
        has not declared ``supports_columnar`` gets per-event objects,
        minted HERE — explicitly, attributed to this plugin id in
        models.churn_stats() — rather than implicitly wherever its body
        first touches ``group.events``.  With ``LOONG_COLUMNAR=0`` every
        boundary materializes: the dict path of the side-by-side bench."""
        if self.columnar_capable and columnar_enabled():
            return
        if getattr(self.plugin, "requires_columnar", False):
            # columnar-ONLY stage (multiline split/merge): materializing
            # here would no-op the stage — the dict path materializes at
            # the next row-capable boundary instead
            return
        where = self.plugin_id or self.plugin.name
        for g in groups:
            if g.is_columnar() and not g._events:
                g.materialize(where)

    def process(self, groups: List[PipelineEventGroup]) -> None:
        self._materialize_boundary(groups)
        n_in = sum(len(g) for g in groups)
        self.in_events.add(n_in)
        self.in_bytes.add(sum(g.data_size() for g in groups))
        tracer = trace.active_tracer()
        sp = (tracer.start_stage("processor",
                                 "processor." + self.plugin.name)
              if tracer is not None else None)
        prof.push_marker("plugin", self.plugin_id or self.plugin.name)
        t0 = time.perf_counter()
        ok = False
        try:
            self.plugin.process_many(groups)
            ok = True
        finally:
            dt = time.perf_counter() - t0
            prof.pop_marker()
            self.stage_hist.observe(dt)
            self.cost_ms.add(int(dt * 1000))
            if sp is not None:
                sp.end(None if ok else "error")
            if ledger.is_on():
                self._ledger_delta(n_in, groups)
        self.out_events.add(sum(len(g) for g in groups))

    # -- async device plane (split dispatch/complete) -----------------------

    def process_dispatch(self, groups: List[PipelineEventGroup]):
        self._materialize_boundary(groups)
        n_in = sum(len(g) for g in groups)
        self.in_events.add(n_in)
        self.in_bytes.add(sum(g.data_size() for g in groups))
        tracer = trace.active_tracer()
        sp = (tracer.start_stage("processor",
                                 "processor." + self.plugin.name
                                 + ".dispatch")
              if tracer is not None else None)
        prof.push_marker("plugin", self.plugin_id or self.plugin.name)
        t0 = time.perf_counter()
        ok = False
        try:
            tokens = [self.plugin.process_dispatch(g) for g in groups]
            ok = True
        finally:
            dt = time.perf_counter() - t0
            prof.pop_marker()
            self.stage_hist.observe(dt)
            self.cost_ms.add(int(dt * 1000))
            if sp is not None:
                sp.end(None if ok else "error")
            if ledger.is_on():
                self._ledger_delta(n_in, groups)
        return tokens

    def process_complete(self, groups: List[PipelineEventGroup],
                         tokens) -> None:
        n_in = sum(len(g) for g in groups)
        tracer = trace.active_tracer()
        sp = (tracer.start_stage("processor",
                                 "processor." + self.plugin.name
                                 + ".complete")
              if tracer is not None else None)
        prof.push_marker("plugin", self.plugin_id or self.plugin.name)
        t0 = time.perf_counter()
        ok = False
        try:
            for g, tok in zip(groups, tokens):
                self.plugin.process_complete(g, tok)
            ok = True
        finally:
            dt = time.perf_counter() - t0
            prof.pop_marker()
            self.stage_hist.observe(dt)
            self.cost_ms.add(int(dt * 1000))
            if sp is not None:
                sp.end(None if ok else "error")
            if ledger.is_on():
                self._ledger_delta(n_in, groups)
        self.out_events.add(sum(len(g) for g in groups))


class InputInstance:
    def __init__(self, plugin: Input, plugin_id: str = ""):
        self.plugin = plugin
        self.plugin_id = plugin_id
        self.metrics = MetricsRecord(
            category="plugin",
            labels={"plugin_type": plugin.name, "plugin_id": plugin_id})

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        self.plugin.metrics_record = self.metrics
        return self.plugin.init(config, context)

    def start(self) -> bool:
        return self.plugin.start()

    def stop(self, is_pipeline_removing: bool = False) -> bool:
        return self.plugin.stop(is_pipeline_removing)


class FlusherInstance:
    def __init__(self, plugin: Flusher, plugin_id: str = ""):
        self.plugin = plugin
        self.plugin_id = plugin_id
        self.metrics = MetricsRecord(
            category="plugin",
            labels={"plugin_type": plugin.name, "plugin_id": plugin_id})
        self.in_events = self.metrics.counter("in_events_total")
        self.in_groups = self.metrics.counter("in_event_groups_total")

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        self.plugin.metrics_record = self.metrics
        return self.plugin.init(config, context)

    def send(self, group: PipelineEventGroup) -> bool:
        # loongcolumn: the sink-side lazy materialization boundary — a
        # sink without columnar-capable serialization gets per-event
        # objects here (counted), the NDJSON/SLS-riding family never does
        if group.is_columnar() and not group._events \
                and not (columnar_enabled()
                         and getattr(self.plugin, "supports_columnar",
                                     False)):
            group.materialize(self.plugin_id or self.plugin.name)
        self.in_events.add(len(group))
        self.in_groups.add(1)
        # batch + serialize + sender-queue enqueue all live under the
        # flusher plugin's send — one span covers the serialize stage
        tracer = trace.active_tracer()
        sp = (tracer.start_stage("flusher", "flusher.send",
                                 attrs={"flusher": self.plugin.name,
                                        "events": len(group)})
              if tracer is not None else None)
        ok = False
        try:
            result = self.plugin.send(group)
            ok = True
            if getattr(self.plugin, "ledger_terminal", False):
                # delivery (or refusal) completed inside send(): terminal
                # for the SOURCE span regardless of ledger state
                ack_watermark.ack_groups([group])
                if slo.is_on():
                    slo.observe_groups(
                        self.plugin._ledger_pipeline(), [group],
                        slo.OUTCOME_SEND_OK if result
                        else slo.OUTCOME_DROP)
            if ledger.is_on() and self.plugin.ledger_terminal:
                # inline-terminal sink: delivery completed (or was refused)
                # inside send() itself — ledger it here, once, centrally
                pname = self.plugin._ledger_pipeline()
                if result:
                    ledger.record(pname, ledger.B_SEND_OK, len(group),
                                  group.data_size(), tag=self.plugin.name)
                else:
                    ledger.record(pname, ledger.B_DROP, len(group),
                                  group.data_size(), tag="send_rejected")
            return result
        finally:
            if sp is not None:
                sp.end(None if ok else "error")

    def start(self) -> bool:
        return self.plugin.start()

    def stop(self, is_pipeline_removing: bool = False) -> bool:
        return self.plugin.stop(is_pipeline_removing)

    @property
    def queue_key(self) -> int:
        return self.plugin.queue_key
