"""processor_filter — keep/drop events by field regex conditions.

Reference: core/plugin/processor/ProcessorFilterNative.cpp — Include map
(field → full-match regex, all must match) and Exclude map (any match drops).

TPU path: per-field match via RegexEngine.match_batch (segment/DFA tier on
device); columnar groups drop events by boolean-mask compaction of the span
columns — no per-event objects.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from ..models import ColumnarLogs, PipelineEventGroup
from ..ops.regex.engine import RegexEngine, get_engine
from ..pipeline.plugin.interface import PluginContext, Processor
from .common import extract_source


def compact_columns(cols: ColumnarLogs, keep: np.ndarray) -> ColumnarLogs:
    out = ColumnarLogs(cols.offsets[keep], cols.lengths[keep],
                       cols.timestamps[keep])
    for name, (offs, lens) in cols.fields.items():
        out.set_field(name, offs[keep], lens[keep])
    if cols.parse_ok is not None:
        out.parse_ok = cols.parse_ok[keep]
    out.content_consumed = cols.content_consumed
    return out


class ProcessorFilter(Processor):
    name = "processor_filter_native"
    supports_columnar = True

    def __init__(self) -> None:
        super().__init__()
        self.include: List = []   # [(key bytes, engine)]
        self.exclude: List = []

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        super().init(config, context)
        for k, pattern in (config.get("Include") or {}).items():
            self.include.append((k.encode(), get_engine(pattern)))
        for k, pattern in (config.get("Exclude") or {}).items():
            self.exclude.append((k.encode(), get_engine(pattern)))
        return True

    def fused_stage_spec(self, ctx):
        """loongresident: the whole Include/Exclude condition set joins a
        fused pipeline program as ONE ``keep`` stage — each condition a
        DFA/Tier-1 match over the packed source rows or, for a field a
        prior member's extract stage produced, a span-bound DFA over that
        stage's DEVICE-RESIDENT capture column.  The combined keep mask
        is computed on device; the apply is pure column compaction.  Any
        condition that cannot bind statically (field minted outside the
        run, consumed source, CPU-tier pattern with no DFA form) refuses
        fusion and the filter keeps its per-stage path."""
        if not self.include and not self.exclude:
            return None
        from ..ops import fused_pipeline as fp
        from ..ops.regex.dfa import DFAUnsupported, compile_dfa
        from ..ops.regex.program import PatternTier
        from ..pipeline.fused_chain import FusedMemberStage
        conds = []
        for negate, pairs in ((False, self.include), (True, self.exclude)):
            for key, engine in pairs:
                binding = ctx.resolve(key)
                if binding is None:
                    return None
                if binding == "source":
                    if not ctx.bind_source(key):
                        return None
                    if engine.tier is PatternTier.SEGMENT:
                        conds.append(fp.StageCond(
                            "extract_ok", engine._segment_kernel.program,
                            ["extract_ok", engine.pattern, negate],
                            negate=negate, staged=engine._segment_kernel))
                    elif engine.tier is PatternTier.DFA:
                        conds.append(fp.StageCond(
                            "match", engine._dfa_kernel.dfa,
                            ["match", engine.pattern, negate],
                            negate=negate, staged=engine._dfa_kernel))
                    else:
                        return None
                else:
                    _tag, prod, cap = binding
                    try:
                        dfa = compile_dfa(engine.pattern)
                    except DFAUnsupported:
                        return None
                    from ..ops.kernels.dfa_scan import LazySpanMatchKernel
                    conds.append(fp.StageCond(
                        "span_match", dfa,
                        ["span_match", engine.pattern, prod, cap, negate],
                        binding=(prod, cap), negate=negate,
                        staged=LazySpanMatchKernel(dfa)))
        spec = fp.StageSpec("keep", conds,
                            ["keep"] + [list(c.ident) for c in conds],
                            label="filter")
        return FusedMemberStage(spec, self._fused_apply)

    def _fused_apply(self, group, src, out, rowmap):
        keep = np.asarray(out[0], dtype=bool)[rowmap]
        if src.undecided is not None:
            # rows whose spans the device did not produce (a json_fields
            # producer handed them to the host's emitter): the device's
            # mask says nothing of them, the staged matcher decides them
            # from the columns the emitter installed.  An extract producer
            # leaves none: a row it did not parse has no field to match.
            rows = np.nonzero(src.undecided[rowmap])[0]
            if len(rows):
                keep[rows] = self._host_keep(group, rows)
        if keep.all():
            return rowmap
        cols = group.columns
        if cols is not None and not group._events:
            group.set_columns(compact_columns(cols, keep))
        else:
            group._events = [ev for i, ev in enumerate(group.events)
                             if keep[i]]
        return rowmap[keep]

    def _match_field(self, group: PipelineEventGroup, key: bytes,
                     engine: RegexEngine, n: int, rows=None) -> np.ndarray:
        src = extract_source(group, key)
        if src is None:
            return np.zeros(n, dtype=bool)
        offsets, lengths, present = src.offsets, src.lengths, src.present
        if rows is not None:
            offsets, lengths, present = \
                offsets[rows], lengths[rows], present[rows]
        ok = engine.match_batch(src.arena, offsets, lengths)
        return ok & present

    def _host_keep(self, group: PipelineEventGroup, rows=None) -> np.ndarray:
        """The staged decision for every event of the group, or for the
        events ``rows`` (indices) alone."""
        n = len(group) if rows is None else len(rows)
        keep = np.ones(n, dtype=bool)
        for key, engine in self.include:
            keep &= self._match_field(group, key, engine, n, rows)
        for key, engine in self.exclude:
            keep &= ~self._match_field(group, key, engine, n, rows)
        return keep

    def process(self, group: PipelineEventGroup) -> None:
        if len(group) == 0:
            return
        keep = self._host_keep(group)
        if keep.all():
            return
        cols = group.columns
        if cols is not None and not group._events:
            group.set_columns(compact_columns(cols, keep))
        else:
            group._events = [ev for i, ev in enumerate(group.events) if keep[i]]
