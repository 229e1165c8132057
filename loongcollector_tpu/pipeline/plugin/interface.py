"""Plugin interfaces: Input / Processor / Flusher.

Reference: core/collection_pipeline/plugin/interface/{Input,Processor,
Flusher}.h — Init(config, context), Start/Stop for inputs, Process(group) for
processors, Send(group)/FlushAll for flushers.  Flusher::Send serializes into
its own sender queue (interface/Flusher.cpp:57).
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional

from ... import trace
from ...models import PipelineEventGroup
from ...monitor import ledger, slo
from ...runner import ack_watermark


class PluginContext:
    """Per-pipeline context handed to every plugin instance (reference
    CollectionPipelineContext)."""

    def __init__(self, pipeline_name: str = "", config: Optional[dict] = None):
        self.pipeline_name = pipeline_name
        self.config = config or {}
        self.process_queue_key: int = 0
        self.process_queue_manager = None  # set by CollectionPipeline.init
        self.global_config: Dict[str, Any] = {}
        self.logger = None
        self.metrics = None
        self.pipeline = None  # set by CollectionPipeline.init
        # named extension instances from the pipeline's `extensions:`
        # section (reference pkg/pipeline/extensions); key = "<type>" or
        # "<type>/<alias>"
        self.extensions: Dict[str, Any] = {}

    def get_extension(self, ref: str):
        """Resolve an extension reference from another plugin's config."""
        return self.extensions.get(ref)

    # -- plugin checkpoints (reference pkg/pipeline/context.go
    #    GetCheckPoint/SaveCheckPoint) -------------------------------------

    def get_checkpoint(self, key: str):
        from .checkpoint import get_default_store
        return get_default_store().get(self.pipeline_name, key)

    def save_checkpoint(self, key: str, value: str) -> None:
        from .checkpoint import get_default_store
        get_default_store().save(self.pipeline_name, key, value)


class Plugin:
    name: str = "plugin_base"

    def __init__(self) -> None:
        self.context: Optional[PluginContext] = None
        self.metrics_record = None
        self.config: Dict[str, Any] = {}

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        self.context = context
        self.config = config
        return True


class Input(Plugin):
    """Inputs register with their singleton runner on start (reference
    Input::Start registers with e.g. FileServer / PrometheusInputRunner)."""

    name = "input_base"
    is_singleton = False   # singleton inputs: one instance across pipelines
    is_onetime = False     # onetime inputs: finite jobs with expiry

    def start(self) -> bool:  # pragma: no cover - interface
        return True

    def stop(self, is_pipeline_removing: bool = False) -> bool:
        return True

    def supported_event_types(self) -> List[str]:
        return ["log"]


class Processor(Plugin):
    """Process mutates the group in place (reference Processor.h:28-37).

    Device-backed processors additionally implement the split dispatch /
    complete protocol (`supports_async_dispatch = True`): `process_dispatch`
    starts the device work and returns an opaque token; `process_complete`
    materialises it and applies the results.  The runner overlaps the device
    execution of group N with the host stages of its neighbours (SURVEY §7
    step 4 — the async device data plane)."""

    name = "processor_base"
    supports_async_dispatch = False

    #: loongcolumn capability flag: True ⇒ this plugin operates on
    #: ColumnarLogs span columns directly and never needs per-event dict
    #: access — columnar groups flow THROUGH it unmaterialized.  False ⇒
    #: the ProcessorInstance wrapper materializes per-event objects at
    #: this plugin's boundary (counted in models.churn_stats()) before
    #: calling it.  Declare it only when BOTH code paths are exercised by
    #: the columnar-vs-dict equivalence gate
    #: (scripts/columnar_equivalence.py).
    supports_columnar = False

    #: True ⇒ this plugin ONLY understands span columns (no row path at
    #: all: the multiline split/merge family) — the instance wrapper must
    #: never materialize at its boundary, even in dict mode
    #: (``LOONG_COLUMNAR=0``), or the stage silently no-ops.  Implies
    #: supports_columnar.
    requires_columnar = False

    def process(self, group: PipelineEventGroup) -> None:  # pragma: no cover
        raise NotImplementedError

    def process_many(self, groups: List[PipelineEventGroup]) -> None:
        for g in groups:
            self.process(g)

    def process_dispatch(self, group: PipelineEventGroup):
        """Start work on `group`; device work may remain in flight.  The
        default (sync plugins) runs to completion and returns no token."""
        self.process(group)
        return None

    def fused_stage_spec(self, ctx):
        """loongresident: this plugin's device work in resident stage form
        (pipeline/fused_chain.FusedMemberStage), or None when it cannot
        join a fused pipeline program — not device-tier, inputs not
        statically bindable against ``ctx`` (FusionPlanContext), or the
        plugin simply has no device half.  Returning a member DOES NOT
        change the plugin's own process path: groups fusion cannot take
        still run it per-stage."""
        return None

    def process_complete(self, group: PipelineEventGroup, token) -> None:
        """Finish the work started by process_dispatch."""


class Flusher(Plugin):
    name = "flusher_base"

    #: loongledger: True for sinks whose ``send()`` terminates delivery
    #: inline (local file, stdout, blackhole, test checkers) — the
    #: FlusherInstance wrapper then ledgers ``send_ok`` centrally.  Sinks
    #: that queue/batch toward a network hop keep False and ledger at
    #: their real delivery boundary instead.
    ledger_terminal = False

    #: loongcolumn capability flag (the flusher-side mirror of
    #: Processor.supports_columnar): True ⇒ this sink's serialize path
    #: consumes span columns directly (the NDJSON-riding family, SLS wire,
    #: blackhole), so columnar groups reach the wire without ever minting
    #: per-event objects.  False ⇒ FlusherInstance materializes at send().
    supports_columnar = False

    def _ledger_pipeline(self) -> str:
        """Pipeline attribution for this sink's ledger records ("" when
        the flusher was never init()ed — tests driving bare plugins)."""
        return getattr(getattr(self, "context", None),
                       "pipeline_name", "") or ""

    def _ledger_drop(self, tag: str, n_events: int = 0, n_bytes: int = 0,
                     group: Optional[PipelineEventGroup] = None) -> None:
        """Reason-tagged terminal ``drop`` record for events this flusher
        discards — the shared shape of the B_DROP boilerplate.  Pass
        ``group`` to defer the O(events) count/size work until the ledger
        is confirmed on (the disabled-hook idiom)."""
        if group is not None:
            # a reasoned discard is terminal for the SOURCE span too: the
            # checkpoint watermark must advance past it (ledger on or off)
            ack_watermark.ack_groups([group], force=True)
            if slo.is_on():
                slo.observe_groups(self._ledger_pipeline(), [group],
                                   slo.OUTCOME_DROP)
        if not ledger.is_on():
            return
        if group is not None:
            n_events, n_bytes = len(group), group.data_size()
        ledger.record(self._ledger_pipeline(), ledger.B_DROP,
                      n_events, n_bytes, tag=tag)

    def _ledger_terminal_write(self, groups: List[PipelineEventGroup],
                               write_fn) -> bool:
        """Run ``write_fn()`` — the sink's actual write of ``groups`` —
        with the write-through terminal accounting around it: B_SEND_OK
        once the write lands, B_DROP tag=flush_write_failed when it
        raises.  The failure is terminal HERE (recorded + logged, not
        re-raised): the batch already left the batcher, nothing upstream
        can retry it, and an exception propagating into
        ProcessorRunner._send would record a second terminal
        (``send_error``) for the triggering group — a double count the
        auditor would report as a (negative) residual.  Returns False on
        a failed write."""
        led = ledger.is_on()
        if led:
            n_events = sum(len(g) for g in groups)
            n_bytes = sum(g.data_size() for g in groups)
        try:
            write_fn()
        except Exception:  # noqa: BLE001
            from ...utils.logger import get_logger
            get_logger("flusher").exception(
                "%s flush write failed; %d events dropped", self.name,
                sum(len(g) for g in groups))
            # terminal either way (nothing upstream retries a failed
            # write): the SOURCE spans are done — ack so the checkpoint
            # can advance instead of pinning on a dead batch
            ack_watermark.ack_groups(groups)
            if slo.is_on():
                slo.observe_groups(self._ledger_pipeline(), groups,
                                   slo.OUTCOME_DROP)
            if led:
                ledger.record(self._ledger_pipeline(), ledger.B_DROP,
                              n_events, n_bytes, tag="flush_write_failed")
            return False
        ack_watermark.ack_groups(groups)
        if slo.is_on():
            slo.observe_groups(self._ledger_pipeline(), groups,
                               slo.OUTCOME_SEND_OK)
        if led:
            ledger.record(self._ledger_pipeline(), ledger.B_SEND_OK,
                          n_events, n_bytes, tag=self.name)
        return True

    def _serialize_and_write(self, groups: List[PipelineEventGroup],
                             serialize_fn, write_fn) -> bool:
        """The flush of a write-through sink (file, stdout):
        ``write_fn(serialize_fn(groups))`` under the terminal accounting
        above."""
        return self._ledger_terminal_write(
            groups, lambda: self._serialize_then_write(
                groups, serialize_fn, write_fn))

    def _serialize_then_write(self, groups: List[PipelineEventGroup],
                              serialize_fn, write_fn) -> None:
        """``write_fn(serialize_fn(groups))``, each half under a span of
        its own — ``flusher.serialize`` and ``flusher.write``.
        flusher_file runs it on its sender thread
        (flusher/flush_sender.py), where the two spans are always
        rootless; flusher_stdout runs it inline in the batcher's flush,
        where they are children of ``flusher.send`` when the count trigger
        fires on the worker and rootless from the batcher's timeout
        thread."""
        tracer = trace.active_tracer()
        if tracer is None:
            write_fn(serialize_fn(groups))
            return
        attrs = {"flusher": self.name, "groups": len(groups),
                 "events": sum(len(g) for g in groups)}
        none = contextlib.nullcontext()
        sp = tracer.child_or_sampled("flusher", "flusher.serialize", attrs)
        with sp or none:
            data = serialize_fn(groups)
            attrs["nbytes"] = len(data)
            if sp is not None:
                sp.set_attr("nbytes", len(data))
        with tracer.child_or_sampled("flusher", "flusher.write",
                                     attrs) or none:
            write_fn(data)

    def __init__(self) -> None:
        super().__init__()
        self.queue_key: int = 0
        self.sender_queue = None
        self.plugin_id: str = ""  # set by the pipeline: "<type>/<index>"

    def spill_identity(self) -> Dict[str, str]:
        """Identity persisted with disk-buffered payloads; must uniquely
        address this flusher instance within its pipeline."""
        return {
            "pipeline": getattr(self.context, "pipeline_name", ""),
            "flusher_type": self.name,
            "plugin_id": self.plugin_id,
        }

    def send(self, group: PipelineEventGroup) -> bool:  # pragma: no cover
        raise NotImplementedError

    def flush(self, key: int = 0) -> bool:
        return True

    def flush_all(self) -> bool:
        return True

    def start(self) -> bool:
        return True

    def stop(self, is_pipeline_removing: bool = False) -> bool:
        return True
