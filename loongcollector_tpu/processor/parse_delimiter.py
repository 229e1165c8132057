"""processor_parse_delimiter — delimited fields via the TPU segment kernel.

Reference: core/plugin/processor/ProcessorParseDelimiterNative.cpp (single /
multi-char separators; quote mode via the CSV FSM in
core/parser/DelimiterModeFsmParser.h:27-56).

TPU redesign: a non-quoted delimiter split IS a Tier-1 segment program —
`([^d]*)d([^d]*)d...(.*)` — so it runs on the same gather-free extraction
kernel as regex parse.  Quote mode (loongstruct) runs on the
structural-index plane: `lct_delim_struct_parse` derives field spans from
quote/separator bitmaps with the doubled-quote rule resolved in the same
carry pass, retiring the per-row Python FSM for columnar groups — fields
needing byte rewrites (doubled quotes, quoted-head + tail) decode once
into a per-group side arena.  Without the native library, the numpy twin
(ops/kernels/struct_index.py) indexes the batch and a vectorised emitter
covers the RFC4180-clean subset; only index-deviant rows walk the
reference FSM per row (counted in `parse_fallback_rows_total`).
`_csv_fsm_split` remains the per-row semantic reference and the row-group
/ deviant-row tier.
"""

from __future__ import annotations

import re as _re
from typing import Any, Dict, List

import numpy as np

from ..models import PipelineEventGroup
from ..ops.regex.engine import RegexEngine, get_engine
from ..pipeline.plugin.interface import PluginContext, Processor
from .common import (RAW_LOG_KEY, apply_parse_spans,
                     extract_source, finish_row_keep)


class _SpanResult:
    """BatchParseResult-shaped container for apply_parse_spans."""

    __slots__ = ("ok", "cap_off", "cap_len")

    def __init__(self, ok, cap_off, cap_len):
        self.ok = ok
        self.cap_off = cap_off
        self.cap_len = cap_len


def _csv_fsm_split(data: bytes, sep: bytes, quote: int = 0x22) -> List[bytes]:
    """Quote-mode split (reference DelimiterModeFsmParser state table):
    fields may be quoted; doubled quotes inside quoted fields escape."""
    fields: List[bytes] = []
    cur = bytearray()
    in_quote = False
    i, n = 0, len(data)
    s = sep[0]
    while i < n:
        b = data[i]
        if in_quote:
            if b == quote:
                if i + 1 < n and data[i + 1] == quote:
                    cur.append(quote)
                    i += 1
                else:
                    in_quote = False
            else:
                cur.append(b)
        elif b == quote and not cur:
            in_quote = True
        elif b == s and data[i : i + len(sep)] == sep:
            fields.append(bytes(cur))
            cur = bytearray()
            i += len(sep) - 1
        else:
            cur.append(b)
        i += 1
    fields.append(bytes(cur))
    return fields


class ProcessorParseDelimiter(Processor):
    name = "processor_parse_delimiter_tpu"
    supports_columnar = True

    def __init__(self) -> None:
        super().__init__()
        self.source_key = b"content"
        self.separator = b","
        self.quote_mode = False
        self.keys: List[str] = []
        self.keep_source_on_fail = True
        self.keep_source_on_success = False
        self.renamed_source_key = RAW_LOG_KEY
        self.engine: RegexEngine = None  # type: ignore
        self.allow_not_enough = False
        self._pipeline = ""

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        super().init(config, context)
        self.source_key = config.get("SourceKey", "content").encode()
        sep = config.get("Separator", ",")
        self.separator = sep.encode() if isinstance(sep, str) else bytes(sep)
        self.quote_mode = bool(config.get("Quote", "")) or \
            config.get("Mode", "") == "quote"
        self.keys = list(config.get("Keys", []))
        self.keep_source_on_fail = bool(config.get("KeepingSourceWhenParseFail", True))
        self.keep_source_on_success = bool(config.get("KeepingSourceWhenParseSucceed", False))
        self.renamed_source_key = config.get("RenamedSourceKey", RAW_LOG_KEY)
        self.allow_not_enough = bool(config.get("AcceptNoEnoughKeys", False))
        self._pipeline = getattr(context, "pipeline_name", "") or ""
        if not self.keys:
            return False
        if not self.quote_mode:
            # ([^s]*)s([^s]*)s...s(.*)  — Tier-1; last field takes the rest
            esc = _re.escape(self.separator.decode("latin-1"))
            neg = f"[^{esc}]" if len(self.separator) == 1 else None
            if neg is not None:
                parts = [f"({neg}*)"] * (len(self.keys) - 1) + ["(.*)"] \
                    if len(self.keys) > 1 else ["(.*)"]
                pattern = esc.join(parts)
                self.engine = get_engine(pattern)
        return True

    supports_async_dispatch = True

    def fused_stage_spec(self, ctx):
        """loongresident: the non-quote delimiter split IS a Tier-1
        segment program, so it joins a fused pipeline program exactly
        like regex extraction — same stage kind, same content identity
        (two plugins with the same derived pattern share one compiled
        program).  Quote mode keeps the structural-index plane."""
        from ..ops.regex.program import PatternTier
        eng = self.engine
        if self.quote_mode or self.allow_not_enough or eng is None \
                or eng.tier is not PatternTier.SEGMENT \
                or eng._segment_kernel is None:
            return None
        if not ctx.bind_source(self.source_key):
            return None
        from ..ops import fused_pipeline as fp
        from ..pipeline.fused_chain import FusedMemberStage
        spec = fp.StageSpec("extract", eng._segment_kernel.program,
                            ["extract", eng.pattern],
                            staged=eng._segment_kernel,
                            label=f"extract:{self.name}")
        ctx.note_fields(ctx.n_stages, self.keys[:eng.num_caps])
        ctx.note_consumed(self.source_key)
        return FusedMemberStage(spec, self._fused_apply)

    def _fused_apply(self, group, src, out, rowmap):
        from .common import subset_source
        ok, off, ln = out
        self._apply_device(group, subset_source(src, rowmap),
                           _SpanResult(ok[rowmap], off[rowmap], ln[rowmap]))
        return rowmap

    def process_dispatch(self, group: PipelineEventGroup):
        """Async device plane (same split as processor_parse_regex_tpu):
        the delimiter segment program dispatches now, the spans apply in
        process_complete while the device moves on to the next group.
        Quote-mode columnar groups take the synchronous structural-index
        plane instead (span derivation IS the whole computation there)."""
        if self.quote_mode and len(self.separator) == 1 and self.keys:
            # row groups skip the source pack entirely (extract_source
            # would copy every event's bytes just to be discarded) and go
            # straight to the per-event host tier
            if group.columns is None or group._events:
                self._process_host(group)
                return None
            src = extract_source(group, self.source_key)
            if src is None:
                return None
            if src.columnar and self._process_quote_struct(group, src):
                return None
            self._process_host(group)
            return None
        if self.engine is None or self.quote_mode or self.allow_not_enough:
            # configs that can never take the device path skip the source
            # row-pack entirely (extract_source copies every event's bytes
            # on row groups just to be discarded here otherwise)
            self._process_host(group)
            return None
        src = extract_source(group, self.source_key)
        if src is None:
            return None
        if not src.columnar:
            self._process_host(group)
            return None
        pending = self.engine.parse_batch_async(
            src.arena, src.offsets, src.lengths)
        if pending.done:
            self._apply_device(group, src, pending.result())
            return None
        return src, pending

    def process_complete(self, group: PipelineEventGroup, token) -> None:
        if token is None:
            return
        src, pending = token
        self._apply_device(group, src, pending.result())

    def process(self, group: PipelineEventGroup) -> None:
        self.process_complete(group, self.process_dispatch(group))

    def _apply_device(self, group: PipelineEventGroup, src, res) -> None:
        apply_parse_spans(group, src, res, self.keys,
                          self.keep_source_on_fail,
                          self.keep_source_on_success,
                          self.renamed_source_key,
                          source_key=self.source_key)

    # -- quote mode: structural-index plane ---------------------------------

    def _process_quote_struct(self, group: PipelineEventGroup, src) -> bool:
        """Quote-mode CSV from the structural index: native fused walk
        when the library is loaded, else numpy-twin masks + the vectorised
        clean-subset emitter with a counted per-row FSM tier for deviant
        rows.  Returns False only when no structural tier applies (caller
        falls back to the per-row host path wholesale)."""
        from .. import native as _native
        F = len(self.keys)
        n = len(src.offsets)
        sep = self.separator[0]
        sb = group.source_buffer
        arena_len = len(src.arena)
        n_fallback = 0

        res = _native.delim_struct_parse(src.arena, src.offsets,
                                         src.lengths, sep, 0x22, F)
        if res is not None:
            from .common import append_side_arena, rebase_side_spans
            cap_off, cap_len, nfields, side = res
            rebase = append_side_arena(sb, side, arena_len)
            cap_off = rebase_side_spans(cap_off, cap_len, arena_len,
                                        rebase)
        else:
            emitted = self._quote_struct_numpy(group, src, F, sep)
            if emitted is None:
                return False
            cap_off, cap_len, nfields, n_fallback = emitted
        ok = nfields >= F
        if self.allow_not_enough:
            ok = nfields >= 1
        self._apply_device(group, src,
                           _SpanResult(ok & src.present, cap_off, cap_len))
        from . import parse_telemetry
        parse_telemetry.note_rows(self.name, self._pipeline,
                                  int(src.present.sum()), n_fallback)
        return True

    def _quote_struct_numpy(self, group, src, F: int, sep: int):
        """No-native tier: numpy-twin index + vectorised emission; rows
        the clean-subset emitter cannot express (doubled quotes, literal
        mid-field quotes, joins) run the reference FSM per row — counted.
        Returns (cap_off, cap_len, nfields, n_fallback) or None."""
        from ..ops.kernels import struct_index as _si
        n = len(src.offsets)
        lengths = np.asarray(src.lengths, dtype=np.int32)
        L = max(1, int(lengths.max()) if n else 1)
        rows = np.zeros((n, L), dtype=np.uint8)
        arena = src.arena
        for i in range(n):
            o, ln = int(src.offsets[i]), int(lengths[i])
            if ln > 0:
                rows[i, :ln] = arena[o : o + ln]
        masks = _si.struct_index_numpy(rows, lengths, mode=_si.MODE_DELIM,
                                       sep=int(sep))
        quote_bits = _si.unpack16(masks[3], L)
        sep_bits = _si.unpack16(masks[1], L)
        cap_off, cap_len, nfields, deviant = _si.emit_delim_spans(
            arena, src.offsets, lengths, quote_bits, sep_bits, F)
        sb = group.source_buffer
        n_fallback = 0
        sep_b = bytes([sep])
        for i in np.nonzero(deviant & src.present)[0]:
            n_fallback += 1
            o, ln = int(src.offsets[i]), int(lengths[i])
            # the counted deviant-row tier under the numpy index (no
            # native library loaded) — parse_fallback_rows_total
            # loonglint: disable=per-row-parse
            fields = _csv_fsm_split(arena[o : o + ln].tobytes(), sep_b)
            nfields[i] = len(fields)
            if len(fields) > F:
                fields = fields[: F - 1] + [sep_b.join(fields[F - 1:])]
            for k in range(F):
                if k < len(fields):
                    view = sb.copy_string(fields[k])
                    cap_off[i, k] = view.offset
                    cap_len[i, k] = view.length
                else:
                    cap_len[i, k] = -1
        return cap_off, cap_len, nfields, n_fallback

    def _process_host(self, group: PipelineEventGroup) -> None:
        # host path: quote-mode FSM or row groups.  Keep/discard follows
        # the reference ordering shared with apply_parse_spans: capture the
        # raw source, delete it unless a key overwrote it, re-add under the
        # renamed key per the keep flags.
        sb = group.source_buffer
        key_bytes = [k.encode() for k in self.keys]
        renamed = self.renamed_source_key.encode()
        for ev in group.events:
            if not hasattr(ev, "get_content"):
                continue
            raw = ev.get_content(self.source_key)
            if raw is None:
                continue
            data = raw.to_bytes()
            # row-path groups (per-event plugins upstream) have no arena
            # to index; the FSM is the semantic reference tier
            # loonglint: disable=per-row-parse
            fields = (_csv_fsm_split(data, self.separator)
                      if self.quote_mode else data.split(self.separator))
            if len(fields) < len(self.keys) and not self.allow_not_enough:
                finish_row_keep(ev, raw, False, self.source_key, False,
                                self.keep_source_on_fail,
                                self.keep_source_on_success, renamed)
                continue
            if len(fields) > len(self.keys):
                head = fields[: len(self.keys) - 1]
                tail = self.separator.join(fields[len(self.keys) - 1:])
                fields = head + [tail]
            overwritten = False
            for key, val in zip(key_bytes, fields):
                ev.set_content(key, sb.copy_string(val))
                if key == self.source_key:
                    overwritten = True
            finish_row_keep(ev, raw, True, self.source_key, overwritten,
                            self.keep_source_on_fail,
                            self.keep_source_on_success, renamed)
