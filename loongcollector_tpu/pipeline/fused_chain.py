"""loongresident pipeline glue: plan and execute fused stage runs.

`plan_fusion` walks a pipeline's processor chain at init time and asks
each plugin for its resident stage form (`Processor.fused_stage_spec`):
a maximal run of ≥ 2 consecutive fusable stages becomes a `FusedRun`
backed by ONE content-addressed `FusedProgramKernel`
(ops/fused_pipeline.py).  At process time the run packs the group's
source column once, dispatches the single fused program per chunk, and
applies each member stage's host-side epilogue in order over a row-index
map (a filter's compaction re-indexes every later member's outputs — the
fused program computed them for ALL packed rows, which is equivalent
because member stages are per-row independent).

Binding rules (`FusionPlanContext`): the run packs ONE source column;
members either consume those same rows or bind a span column a PRIOR
member publishes (device-resident span binding).  The producer is any
stage that publishes ``(ok, off, len)`` columns (`SPAN_STAGES` in
ops/fused_pipeline.py: the regex `extract`, whose column names are its
config's `Keys`, and `json_fields`, whose names are the data's own — it
mints a named capture for each key a later member asks for,
`note_open_fields`).  A stage whose inputs cannot be proven statically —
a field minted outside the run, a source key a prior member consumed —
refuses to fuse and ends the run, and so does one that binds a stage
which publishes no span columns (the planner says which, instead of an
unpack error while the program is traced); those stages keep the
per-stage dispatch path untouched.

Execution contract with CollectionPipeline.process_begin: a run behaves
like one async-dispatch-capable processor (dispatch → token →
complete), so the ProcessorRunner's overlap machinery, the stop/drain
barrier and the ledger's per-plugin delta accounting all keep working;
groups fusion cannot take (row-path groups, overlong rows, disabled
fusion) run the member instances per-stage inline — never dropped,
never reordered."""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import trace
from ..monitor import ledger
from ..ops.device_batch import LENGTH_BUCKETS
from ..ops.fused_pipeline import (SPAN_STAGES, FusedDispatch,
                                  fusion_enabled, get_fused_program,
                                  note_json_rows)
from ..utils.logger import get_logger

log = get_logger("fused_chain")


class FusionPlanContext:
    """What the planner knows while growing one run: the packed source
    column, span columns published by prior members (name →
    (stage_idx, cap_idx)), a prior member whose column names are the
    data's own (it mints a capture for a name on request), and which keys
    a member consumed — the information that decides whether the NEXT
    stage's inputs are statically resident."""

    def __init__(self) -> None:
        self.source_key: Optional[bytes] = None
        self.consumed: set = set()
        self.fields: Dict[str, Tuple[int, int]] = {}
        self.n_stages = 0
        #: the configuration's processors ahead of the run's first member
        #: (an input's inner processors, the line splitters, not counted):
        #: 0 means nothing outside the run has given the group a field
        self.user_stages_ahead = 0
        self._open: Optional[Tuple[int, object]] = None

    def bind_source(self, key: bytes) -> bool:
        """True when this stage may read the run's packed source rows."""
        skey = key.decode("latin-1") if isinstance(key, bytes) else key
        if skey in self.consumed:
            return False
        if self.source_key is None:
            self.source_key = key if isinstance(key, bytes) else key.encode()
            return True
        have = self.source_key.decode("latin-1")
        return skey == have

    def resolve(self, key) -> Optional[object]:
        """'source', ("capture", stage_idx, cap_idx), or None (not
        statically resident — the stage must not fuse)."""
        skey = key.decode("latin-1") if isinstance(key, bytes) else key
        got = self.fields.get(skey)
        if got is not None:
            return ("capture", got[0], got[1])
        if self.source_key is not None \
                and skey == self.source_key.decode("latin-1") \
                and skey not in self.consumed:
            return "source"
        if self.source_key is None:
            # a filter heading the run establishes the source column
            return "source"
        if self._open is not None:
            stage_idx, mint = self._open
            self.fields[skey] = (stage_idx, mint(skey))
            return ("capture",) + self.fields[skey]
        return None

    def note_fields(self, stage_idx: int, names: Sequence[str]) -> None:
        for cap, name in enumerate(names):
            if name:
                self.fields[name] = (stage_idx, cap)

    def note_open_fields(self, stage_idx: int, mint) -> None:
        """A stage whose field names are not known when the run is planned
        (a JSON object's keys): ``mint(name)`` returns the capture index
        under which that stage will publish the named field, and `resolve`
        records it beside the `note_fields` columns when a later member
        asks."""
        self._open = (stage_idx, mint)

    def note_consumed(self, key) -> None:
        skey = key.decode("latin-1") if isinstance(key, bytes) else key
        self.consumed.add(skey)


class FusedMemberStage:
    """One processor's contribution to a run: the resident StageSpec plus
    the host-side epilogue.  ``apply(group, src, stage_out, rowmap)``
    applies this stage's outputs (computed over the ORIGINAL packed rows;
    index via ``rowmap``) to the group and returns the new rowmap."""

    __slots__ = ("spec", "apply")

    def __init__(self, spec, apply):
        self.spec = spec
        self.apply = apply


class FusedRun:
    """A planned run of consecutive fusable stages [head, end) with its
    compiled program (built lazily via the content-addressed cache)."""

    def __init__(self, head: int, end: int, instances, members,
                 source_key: bytes):
        self.head = head
        self.end = end
        self.instances = list(instances)
        self.members: List[FusedMemberStage] = list(members)
        self.source_key = source_key
        self._program = None

    def enabled(self) -> bool:
        return fusion_enabled()

    def program(self):
        if self._program is None:
            self._program = get_fused_program(
                [m.spec for m in self.members])
        return self._program

    # -- execution ----------------------------------------------------------

    def dispatch(self, groups) -> List:
        """Per-group tokens; a group fusion cannot take runs the member
        instances per-stage INLINE here (synchronously — the fused plane's
        exception path, not its steady state) and gets a None token.
        The run is one stage to the tracer too: a span of the processors'
        own shape (``processor.fused_chain.dispatch`` / ``.complete``),
        current for its body, so the device legs nest under it."""
        tracer = trace.active_tracer()
        sp = (tracer.start_stage("processor",
                                 "processor.fused_chain.dispatch")
              if tracer is not None else None)
        ok = False
        try:
            tokens: List = []
            for g in groups:
                tok = self._dispatch_group(g)
                if tok is None:
                    for inst in self.instances:
                        inst.process([g])
                tokens.append(tok)
            ok = True
            return tokens
        finally:
            if sp is not None:
                sp.end(None if ok else "error")

    def _dispatch_group(self, group):
        from ..processor.common import extract_source
        src = extract_source(group, self.source_key)
        if src is None or not src.columnar or len(src.offsets) == 0:
            return None
        if int(src.lengths.max()) > LENGTH_BUCKETS[-1]:
            # overlong rows keep the per-stage path (its CPU fallback
            # machinery owns them)
            if any(m.spec.kind == "json_fields" for m in self.members):
                note_json_rows(len(src.offsets),
                               {"overlong": len(src.offsets)})
            return None
        try:
            d = FusedDispatch(self.program(), src.arena, src.offsets,
                              src.lengths).dispatch()
        except Exception:  # noqa: BLE001 — fusion must never lose a group
            log.exception("fused dispatch failed; group demoted to the "
                          "per-stage path")
            return None
        return (src, d)

    def complete(self, groups, tokens) -> None:
        tracer = trace.active_tracer()
        sp = (tracer.start_stage("processor",
                                 "processor.fused_chain.complete")
              if tracer is not None else None)
        ok = False
        try:
            for g, tok in zip(groups, tokens):
                if tok is not None:
                    self._complete_group(g, *tok)
            ok = True
        finally:
            if sp is not None:
                sp.end(None if ok else "error")

    def _complete_group(self, g, src, d) -> None:
        res = d.result()
        rowmap = np.arange(res.n)
        for inst, member, out in zip(self.instances, self.members,
                                     res.stages):
            # in/out booked per member at ITS apply point, after the
            # previous members' compaction — the same funnel the
            # staged path reports (a fused filter's drop must show as
            # reduced input on the NEXT member, not phantom volume)
            n_before = len(g)
            inst.in_events.add(n_before)
            inst.in_bytes.add(g.data_size())
            t0 = time.perf_counter()
            ok = False
            try:
                rowmap = member.apply(g, src, out, rowmap)
                ok = True
            finally:
                dt = time.perf_counter() - t0
                inst.stage_hist.observe(dt)
                inst.cost_ms.add(int(dt * 1000))
                if ledger.is_on():
                    inst._ledger_delta(n_before, [g])
                if ok:
                    inst.out_events.add(len(g))


def _unbound_span(spec, members) -> Optional[str]:
    """Why a ``keep`` or a ``label`` stage cannot join the run: one of its
    span bindings (a ``span_match`` condition's, the rule list's) names a
    member that publishes no ``(ok, off, len)`` columns, or a capture that
    member does not have.  None when every binding holds."""
    if spec.kind == "keep":
        bindings = [("a condition", c.binding) for c in spec.payload
                    if c.kind == "span_match"]
    elif spec.kind == "label" and spec.binding is not None:
        bindings = [("the rule list", spec.binding)]
    else:
        return None
    for what, (prod, cap) in bindings:
        if not 0 <= prod < len(members):
            return f"{what} binds stage {prod}, which is not a " \
                   f"prior member of the run"
        producer = members[prod].spec
        if producer.kind not in SPAN_STAGES:
            return f"{what} binds capture {cap} of stage {prod} " \
                   f"({producer.label}), which publishes no span columns " \
                   f"(only {', '.join(SPAN_STAGES)} stages do)"
        if not 0 <= cap < producer.payload.num_caps:
            return f"{what} binds capture {cap} of stage {prod} " \
                   f"({producer.label}), which publishes " \
                   f"{producer.payload.num_caps}"
    return None


def plan_fusion(chain, n_inner: int = 0) -> List[FusedRun]:
    """Walk the processor chain (its first ``n_inner`` members the inputs'
    inner processors); every maximal run of ≥ 2 consecutive
    stages whose plugins produce a statically-bindable StageSpec becomes
    a FusedRun.  Planning is description — no jit, no device transfers
    (capture-bound filter conditions pay one host-side DFA determinize to
    prove fusability; their staged kernels build lazily on first
    demotion); the fused program compiles on first dispatch (or from the
    warm cache)."""
    runs: List[FusedRun] = []
    i = 0
    n = len(chain)
    while i < n:
        ctx = FusionPlanContext()
        ctx.user_stages_ahead = max(i - n_inner, 0)
        members: List[FusedMemberStage] = []
        insts = []
        j = i
        while j < n:
            hook = getattr(chain[j].plugin, "fused_stage_spec", None)
            ms = None
            if hook is not None:
                try:
                    ms = hook(ctx)
                except Exception:  # noqa: BLE001 — a broken spec hook
                    # must degrade to the per-stage path, not kill init
                    log.exception("fused_stage_spec failed for %s",
                                  chain[j].plugin.name)
                    ms = None
            if ms is not None:
                why = _unbound_span(ms.spec, members)
                if why:
                    log.warning("%s does not join the fused run: %s",
                                chain[j].plugin.name, why)
                    ms = None
            if ms is None:
                break
            ctx.n_stages += 1
            members.append(ms)
            insts.append(chain[j])
            j += 1
            if ms.spec.terminal:
                break
        if len(members) >= 2:
            runs.append(FusedRun(i, j, insts, members, ctx.source_key))
            i = j
        else:
            i += 1
    return runs
