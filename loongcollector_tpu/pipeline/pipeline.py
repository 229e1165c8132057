"""CollectionPipeline: config → plugin chain → runtime wiring.

Reference: core/collection_pipeline/CollectionPipeline.cpp —
Init (:77): build inputs/processors/flushers from the registry (:109-204),
wire inner processors supplied by inputs (:236-256), create the process
queue + feedback + sender queues (:306-358), build the router (:453-480).
Start (:393) brings plugins up sink-to-source so no data drops;
Stop (:491) is source-to-sink with a drain wait (:659-677).
Process (:419) runs inner then user processors; Send routes to flushers.
"""

from __future__ import annotations

import itertools
import threading
from typing import Any, Dict, List, Optional

from ..models import PipelineEventGroup
from ..monitor import ledger, slo
from ..monitor.metrics import MetricsRecord
from ..runner import ack_watermark
from ..utils.logger import get_logger
from .plugin.instance import FlusherInstance, InputInstance, ProcessorInstance
from .plugin.interface import PluginContext
from .plugin.registry import PluginRegistry
from .route.router import Router

log = get_logger("pipeline")

_queue_keys = itertools.count(1)


def next_queue_key() -> int:
    return next(_queue_keys)


class _AggTimeoutHook:
    """Adapter letting the aggregator ride TimeoutFlushManager's cadence
    (processor thread 0 drives it, ProcessorRunner.cpp:109-112)."""

    def __init__(self, pipeline: "CollectionPipeline"):
        self._pipeline = pipeline

    def flush_timeout(self) -> None:
        agg = self._pipeline.aggregator
        if agg is None:
            return
        hook = getattr(agg, "flush_timeout", None)
        if hook is not None:
            self._pipeline._send_direct(hook())


class _ProcDrainHook:
    """Periodic drain for processors holding cross-group state (e.g.
    split_multiline's carried open records): groups they release run
    through the REST of the processor chain and the normal send path."""

    def __init__(self, pipeline: "CollectionPipeline", chain_idx: int,
                 inst: ProcessorInstance):
        self._pipeline = pipeline
        self._chain_idx = chain_idx
        self._inst = inst

    def flush_timeout(self) -> None:
        fn = getattr(self._inst.plugin, "flush_timeout_groups", None)
        if fn is not None:
            self._pipeline.drain_from(self._chain_idx, fn())


class CollectionPipeline:
    def __init__(self) -> None:
        self.name = ""
        # loongtenant: reload generation stamp — the manager bumps it per
        # applied config so /debug/status and the flight recorder can name
        # WHICH incarnation of a pipeline an event belongs to.  0 = never
        # managed (tests constructing pipelines directly)
        self.generation = 0
        self.config: Dict[str, Any] = {}
        self.context = PluginContext()
        self.inputs: List[InputInstance] = []
        self.inner_processors: List[ProcessorInstance] = []
        self.processors: List[ProcessorInstance] = []
        self.flushers: List[FlusherInstance] = []
        self.router = Router()
        self.aggregator = None
        self._agg_timeout_hook = _AggTimeoutHook(self)
        self.process_queue_key = 0
        self._fused_runs = []
        self._fused_by_head = {}
        self._in_process_cnt = 0
        self._in_process_zero = threading.Condition()
        self.metrics = None
        self._metric_records = []

    # ------------------------------------------------------------------

    def init(self, name: str, config: Dict[str, Any],
             process_queue_manager=None, sender_queue_manager=None,
             reuse_queue_key: Optional[int] = None) -> bool:
        self.name = name
        self.config = config
        self.context = PluginContext(pipeline_name=name, config=config)
        self.context.pipeline = self
        self.metrics = MetricsRecord(category="pipeline",
                                     labels={"pipeline_name": name})
        self._metric_records.append(self.metrics)
        registry = PluginRegistry.instance()
        registry.load_static_plugins()

        global_cfg = config.get("global", {})
        self.context.global_config = global_cfg

        # extensions FIRST: other plugins resolve them by name at init
        # (reference pkg/pipeline/extensions + plugins/extension/)
        for ecfg in config.get("extensions", []):
            etyp = ecfg.get("Type", "")
            ext = registry.create_extension(etyp)
            if ext is None or not ext.init(ecfg, self.context):
                return self._abort_init()
            key = etyp
            if ecfg.get("Alias"):
                key = f"{etyp}/{ecfg['Alias']}"
            if key in self.context.extensions:
                # silent overwrite would leave the shadowed instance
                # unstoppable and auth with the wrong credentials
                log.error("duplicate extension %r (use Alias)", key)
                return self._abort_init()
            self.context.extensions[key] = ext

        # inputs
        for i, icfg in enumerate(config.get("inputs", [])):
            typ = icfg.get("Type", "")
            plugin = registry.create_input(typ)
            if plugin is None:
                return self._abort_init()
            inst = InputInstance(plugin, plugin_id=f"{typ}/{i}")
            self._metric_records.append(inst.metrics)
            if not inst.init(icfg, self.context):
                return self._abort_init()
            self.inputs.append(inst)
            # inputs may supply inner processors (reference :236-256, e.g.
            # InputFile creates the split/multiline processors)
            for pcfg in getattr(plugin, "inner_processor_configs", lambda: [])():
                ptyp = pcfg.get("Type", "")
                pplugin = registry.create_processor(ptyp)
                if pplugin is None:
                    return self._abort_init()
                pinst = ProcessorInstance(pplugin, plugin_id=f"{ptyp}/inner")
                self._metric_records.append(pinst.metrics)
                if not pinst.init(pcfg, self.context):
                    return self._abort_init()
                self.inner_processors.append(pinst)

        # user processors
        for i, pcfg in enumerate(config.get("processors", [])):
            typ = pcfg.get("Type", "")
            plugin = registry.create_processor(typ)
            if plugin is None:
                return self._abort_init()
            inst = ProcessorInstance(plugin, plugin_id=f"{typ}/{i}")
            self._metric_records.append(inst.metrics)
            if not inst.init(pcfg, self.context):
                return self._abort_init()
            self.processors.append(inst)

        # aggregator stage (reference pkg/pipeline/aggregator.go:24-51 —
        # at most one per pipeline, between processors and flushers)
        agg_cfgs = config.get("aggregators", [])
        if agg_cfgs:
            acfg = agg_cfgs[0]
            atyp = acfg.get("Type", "")
            self.aggregator = registry.create_aggregator(atyp)
            if self.aggregator is None or \
                    not self.aggregator.init(acfg, self.context):
                return self._abort_init()
            from ..pipeline.batch.timeout_flush_manager import \
                TimeoutFlushManager
            TimeoutFlushManager.instance().register(self._agg_timeout_hook)

        # flushers + router
        route_configs = []
        for i, fcfg in enumerate(config.get("flushers", [])):
            typ = fcfg.get("Type", "")
            plugin = registry.create_flusher(typ)
            if plugin is None:
                return self._abort_init()
            inst = FlusherInstance(plugin, plugin_id=f"{typ}/{i}")
            plugin.plugin_id = inst.plugin_id
            self._metric_records.append(inst.metrics)
            plugin.queue_key = next_queue_key()
            self._sender_queue_manager = sender_queue_manager
            if sender_queue_manager is not None:
                plugin.sender_queue = sender_queue_manager.create_or_reuse_queue(
                    plugin.queue_key, pipeline_name=name)
            if not inst.init(fcfg, self.context):
                self.flushers.append(inst)  # ensure _abort_init stops it
                return self._abort_init()
            self.flushers.append(inst)
            route_configs.append((i, fcfg.get("Match")))
        self.router.init(route_configs)

        # processors holding cross-group state get a timeout-drain hook so
        # their held records flush on idle pipelines too
        from ..pipeline.batch.timeout_flush_manager import TimeoutFlushManager
        chain = self.inner_processors + self.processors
        self._drain_hooks = []
        for idx, inst in enumerate(chain):
            if hasattr(inst.plugin, "flush_timeout_groups"):
                hook = _ProcDrainHook(self, idx, inst)
                self._drain_hooks.append(hook)
                TimeoutFlushManager.instance().register(hook)

        # loongresident: plan fused device-stage runs over the final chain
        # (pure description — programs compile on first dispatch / from
        # the content-addressed cache).  LOONG_FUSED gates execution, not
        # planning, so flipping it needs no pipeline reload.
        from .fused_chain import plan_fusion
        self._fused_runs = plan_fusion(self.inner_processors
                                       + self.processors,
                                       len(self.inner_processors))
        self._fused_by_head = {r.head: r for r in self._fused_runs}

        # process queue: a modified pipeline keeps its key so queued groups
        # survive the swap (reference ExactlyOnceQueueManager/QueueKeyManager
        # keep keys stable per config name)
        self.process_queue_key = (reuse_queue_key if reuse_queue_key
                                  else next_queue_key())
        self.context.process_queue_key = self.process_queue_key
        self.context.process_queue_manager = process_queue_manager
        if process_queue_manager is not None:
            from ..pipeline.queue.bounded_queue import DEFAULT_MAX_BYTES
            priority = int(global_cfg.get("Priority", 1))
            capacity = int(global_cfg.get("ProcessQueueCapacity", 20))
            circular = bool(global_cfg.get("CircularProcessQueue", False))
            # loongcolumn: byte watermark next to the group-count bound —
            # 0 disables (pipeline/queue/bounded_queue.py says why)
            max_bytes = int(global_cfg.get("ProcessQueueMaxBytes",
                                           DEFAULT_MAX_BYTES))
            q = process_queue_manager.create_or_reuse_queue(
                self.process_queue_key, priority, capacity, name,
                circular=circular, max_bytes=max_bytes)
        return True

    def _abort_init(self) -> bool:
        """Failed init: release everything already constructed (batchers
        registered with TimeoutFlushManager, sender queues, metric records)."""
        self.release()
        return False

    def release(self) -> None:
        """Free pipeline-owned global registrations.  Called on failed init
        and after stop() by the manager."""
        from ..pipeline.batch.timeout_flush_manager import TimeoutFlushManager
        if self.aggregator is not None:
            TimeoutFlushManager.instance().unregister(self._agg_timeout_hook)
        for hook in getattr(self, "_drain_hooks", []):
            TimeoutFlushManager.instance().unregister(hook)
        for f in self.flushers:
            try:
                f.plugin.stop(True)
            except Exception:  # noqa: BLE001
                pass
        sqm = getattr(self, "_sender_queue_manager", None)
        if sqm is not None:
            for f in self.flushers:
                sqm.mark_for_deletion(f.plugin.queue_key)
        for rec in self._metric_records:
            rec.mark_deleted()

    # ------------------------------------------------------------------

    def start(self) -> None:
        """Sink-to-source order (reference :393-417)."""
        self.start_flushers()
        self.start_inputs()

    def start_flushers(self) -> None:
        """Bring the sink side up.  During a hot reload the manager calls
        this BEFORE the old generation stops: the moment the new
        generation is registered under the name, groups popped from the
        (shared) process queue route through a chain whose flushers are
        already ready — generation N+1 admits before N stops."""
        for f in self.flushers:
            f.start()

    def start_inputs(self) -> None:
        """Bring the source side up.  Deliberately separate from
        start_flushers: during a reload the OLD generation's inputs must
        stop before the new generation's start (two live tails of one
        file would double-read), so the manager sequences
        start_flushers → drain old → start_inputs."""
        for i in self.inputs:
            i.start()

    def stop(self, is_removing: bool = False) -> None:
        """Source-to-sink with drain (reference :491-532,659-677)."""
        for i in self.inputs:
            i.stop(is_removing)
        self.wait_all_items_in_process_finished()
        # release processor-held state (carried multiline records) through
        # the rest of the chain before the final batch flush
        chain = self.inner_processors + self.processors
        for idx, inst in enumerate(chain):
            drain = getattr(inst.plugin, "drain_groups", None)
            if drain is not None:
                self.drain_from(idx, drain())
        self.flush_batch()
        for f in self.flushers:
            f.stop(is_removing)
        for ext in self.context.extensions.values():
            try:
                ext.stop()
            except Exception:  # noqa: BLE001
                pass

    def drain_from(self, chain_idx: int,
                   groups: List[PipelineEventGroup]) -> None:
        """Run released groups through processors AFTER chain_idx, then the
        normal send path (aggregator + router + flushers)."""
        if not groups:
            return
        if ledger.is_on():
            # held events re-enter the chain: the matching credit for the
            # process_drop their holding stage ledgered when it kept them
            ledger.record(self.name, ledger.B_PROCESS_EXPAND,
                          sum(len(g) for g in groups), tag="drain")
        chain = self.inner_processors + self.processors
        for g in groups:
            for inst in chain[chain_idx + 1:]:
                inst.process([g])
        self.send(groups)

    # ------------------------------------------------------------------

    def process(self, groups: List[PipelineEventGroup]) -> None:
        finish = self.process_begin(groups)
        while finish is not None:
            finish = finish()

    def process_begin(self, groups: List[PipelineEventGroup]):
        """Run the processor chain up to and including the first
        device-dispatch-capable processor's dispatch (async device plane,
        SURVEY §7 step 4).

        Returns None when the chain ran to completion synchronously;
        otherwise a zero-arg continuation that materialises the device work
        and walks the chain on.  The continuation returns None when the
        chain is done, or itself again when a later stage left device work
        in flight in its turn (a multiline classify ahead of a regex
        extract): call it until it returns None, each continuation exactly
        once.  While one is outstanding the group counts as in-process for
        the stop/drain barrier (wait_all_items_in_process_finished)."""
        with self._in_process_zero:
            self._in_process_cnt += 1
        if ledger.is_on():
            ledger.record(self.name, ledger.B_PROCESS_IN,
                          sum(len(g) for g in groups),
                          sum(g.data_size() for g in groups))
        try:
            cont = self._walk_chain(groups, 0, allow_async=True)
        except BaseException:
            self._exit_process()
            raise
        if cont is None:
            self._exit_process()
            return None

        def finish():
            nonlocal cont
            try:
                cont = cont()
            except BaseException:
                self._exit_process()
                raise
            if cont is None:
                self._exit_process()
                return None
            return finish
        return finish

    def _walk_chain(self, groups: List[PipelineEventGroup], i: int,
                    allow_async: bool):
        """Index-walk the processor chain from ``i``.  A fused run
        (loongresident) executes as ONE async stage; with ``allow_async``
        a stage that leaves device work in flight returns a continuation
        (the runner's overlap window), which finishes that stage and walks
        on from the next — so a chain that holds a second device stage
        hands back a second continuation, and one that holds none runs its
        rest inline as it always did.  ``allow_async=False`` runs every
        stage to completion here."""
        chain = self.inner_processors + self.processors
        while i < len(chain):
            run = self._fused_by_head.get(i)
            if run is not None and run.enabled():
                tokens = run.dispatch(groups)
                nxt = run.end
                if any(t is not None for t in tokens):
                    if allow_async:
                        def finish_run(run=run, tokens=tokens, nxt=nxt):
                            run.complete(groups, tokens)
                            return self._walk_chain(groups, nxt,
                                                    allow_async=True)
                        return finish_run
                    run.complete(groups, tokens)
                i = nxt
                continue
            inst = chain[i]
            if not getattr(inst.plugin, "supports_async_dispatch", False):
                inst.process(groups)
                i += 1
                continue
            tokens = inst.process_dispatch(groups)
            if all(t is None for t in tokens):
                # nothing stayed in flight (host-tier route / empty
                # groups): finish the chain inline — deferring would
                # only delay the send.  complete() still runs so the
                # instance's out_events/cost metrics stay truthful.
                inst.process_complete(groups, tokens)
                i += 1
                continue
            if allow_async:
                rest_idx = i + 1

                def finish(inst=inst, tokens=tokens, rest_idx=rest_idx):
                    inst.process_complete(groups, tokens)
                    return self._walk_chain(groups, rest_idx,
                                            allow_async=True)
                return finish
            inst.process_complete(groups, tokens)
            i += 1
        return None

    def _exit_process(self) -> None:
        with self._in_process_zero:
            self._in_process_cnt -= 1
            if self._in_process_cnt == 0:
                self._in_process_zero.notify_all()

    def send(self, groups: List[PipelineEventGroup]) -> bool:
        led = ledger.is_on()
        if led:
            ledger.record(self.name, ledger.B_PROCESS_OUT,
                          sum(len(g) for g in groups))
        if self.aggregator is not None:
            n_in = sum(len(g) for g in groups)
            staged: List[PipelineEventGroup] = []
            for g in groups:
                staged.extend(self.aggregator.add(g))
            # groups the aggregator absorbed (folded into rollup state, not
            # passed through) lose span identity here: force-ack so their
            # SOURCE bytes never pin the checkpoint watermark
            staged_ids = {id(s) for s in staged}
            consumed = [g for g in groups if id(g) not in staged_ids]
            if consumed:
                ack_watermark.ack_groups(consumed, force=True)
                if slo.is_on():
                    # absorbed into rollup state: the stamp retires WITHOUT
                    # a sojourn sample — the rollup minted at window close
                    # gets its own stamp (_send_direct) and carries the
                    # delivery latency from there
                    slo.retire_groups(consumed)
            groups = staged
            if led and not getattr(self.aggregator,
                                   "ledger_self_accounting", False):
                # a stateful aggregator holds (delta < 0, a process_drop it
                # repays via _send_direct at flush) or mints rollup events
                # (delta > 0, process_expand) — either way the chain stays
                # balanced without instrumenting every aggregator plugin.
                # Self-accounting aggregators (loongagg's fold) book their
                # own agg_in/agg_fold/agg_emit boundaries instead.
                delta = sum(len(g) for g in groups) - n_in
                if delta < 0:
                    ledger.record(self.name, ledger.B_PROCESS_DROP, -delta,
                                  tag="aggregator")
                elif delta > 0:
                    ledger.record(self.name, ledger.B_PROCESS_EXPAND, delta,
                                  tag="aggregator")
        ok = True
        for group in groups:
            if group.empty():
                # filtered to nothing: terminal for its SOURCE span
                ack_watermark.ack_groups([group], force=True)
                if slo.is_on():
                    slo.retire_groups([group])
                continue
            ok = self._route_group(group, led) and ok
        return ok

    def _route_group(self, group: PipelineEventGroup, led: bool) -> bool:
        idxs = self.router.route(group)
        if not idxs:
            # no flusher matched: the group is terminally discarded
            ack_watermark.ack_groups([group], force=True)
            if led:
                ledger.record(self.name, ledger.B_DROP, len(group),
                              group.data_size(), tag="no_route")
            if slo.is_on():
                slo.observe_groups(self.name, [group], slo.OUTCOME_DROP)
        elif len(idxs) > 1:
            # every extra matching flusher mints a copy of the group's
            # events — raise the span's terminal refcount BEFORE any copy
            # can ack, or a fast first sink advances the watermark while
            # the second copy is still in flight
            ack_watermark.note_fanout(group, len(idxs))
            if led:
                ledger.record(self.name, ledger.B_FANOUT,
                              (len(idxs) - 1) * len(group))
            if slo.is_on():
                # the ingest stamp's refcount mirrors the span fanout: each
                # copy's terminal observes its own sojourn
                slo.note_fanout(group, len(idxs))
        ok = True
        for idx in idxs:
            ok = self.flushers[idx].send(group) and ok
        return ok

    def _send_direct(self, groups: List[PipelineEventGroup]) -> None:
        led = ledger.is_on()
        self_acct = getattr(self.aggregator, "ledger_self_accounting", False)
        for group in groups:
            if group.empty():
                ack_watermark.ack_groups([group], force=True)
                if slo.is_on():
                    slo.retire_groups([group])
                continue
            if slo.is_on():
                # aggregator rollups are minted stampless (the checker's
                # explicit exemption): window close IS their ingest instant
                slo.ensure_stamp(self.name, group)
            if led:
                if not self_acct:
                    # aggregator-held events released by timeout/final
                    # flush: the credit matching the "aggregator"-tagged
                    # process_drop (self-accounting aggregators booked
                    # agg_emit at emission instead)
                    ledger.record(self.name, ledger.B_PROCESS_EXPAND,
                                  len(group), tag="aggregator_flush")
                ledger.record(self.name, ledger.B_PROCESS_OUT, len(group))
            self._route_group(group, led)

    def flush_batch(self) -> None:
        if self.aggregator is not None:
            self._send_direct(self.aggregator.flush())
        for f in self.flushers:
            f.plugin.flush_all()

    def wait_all_items_in_process_finished(self, timeout: float = 10.0) -> bool:
        with self._in_process_zero:
            if self._in_process_cnt == 0:
                return True
            return self._in_process_zero.wait_for(
                lambda: self._in_process_cnt == 0, timeout)

    def has_go_pipeline(self) -> bool:
        return False
