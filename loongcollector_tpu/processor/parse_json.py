"""processor_parse_json — expand a JSON-object field into event fields.

Reference: core/plugin/processor/ProcessorParseJsonNative.cpp (rapidjson
parse of one key into fields, keep/discard semantics shared with regex
parser).

Where it runs.  Despite the plugin's name, until PR 27 this processor
parsed on the HOST in every pipeline (the native plane below); the device
twin of the structural index was driven by tests only.  Now: beside a
fusable neighbour — a `processor_filter_native` on a parsed key directly
behind it, as in example_config/quick_start/json_filter.yaml, or a filter
on the source directly ahead — the parse joins the fused run as a
``json_fields`` device stage (`fused_stage_spec`, ops/kernels/
json_fields.py): the device emits the top-level members' value spans and
the filter's keep mask in one program, `_fused_apply` installs them, and
only the rows the stage cannot prove (escapes, unprovable shapes, not an
object) go through the native emitter below, counted by reason.  A lone
``processor_parse_json_tpu``, one behind another processor, one that keeps
its source on success, and every pipeline on a CPU backend or under
``LOONG_FUSED=0`` keep the host plane, which is:

Execution (loongstruct): columnar groups parse on the structural-index
plane — `lct_json_struct_parse` classifies every row into per-bit
structural bitmaps (simdjson-style escape-carry + in-string prefix-XOR)
and emits field spans straight from the index, so schema-stable AND
schema-drifting AND escape-bearing rows all stay on the columnar
zero-materialization path: string values keep zero-copy spans, escaped
values decode ONCE into a per-group side arena (appended to the source
buffer in one allocation, never per event), unknown keys install from the
CSR extras stream.  Rows the index cannot prove well-formed fall back to
per-row `json.loads` — counted in `parse_fallback_rows_total` and alarmed
via PARSE_FALLBACK_DEGRADED when sustained (docs/device_plane.md
"Structural-index parsing").  Values are raw source tokens
(numbers/bools keep their source spelling); the fallback canonicalises
via str()/json.dumps — the two differ only in number/whitespace spelling
of unusual inputs.  Where the native library is absent the
schema-discovery plane (one stable-schema pass, the rest per row) takes
the group.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict

import numpy as np

from ..models import ColumnarLogs, PipelineEventGroup
from ..pipeline.plugin.interface import PluginContext, Processor
from .common import RAW_LOG_KEY, extract_source


def _column(field_offs, field_lens, name: str, n: int):
    """The (offsets, lengths) pair of field ``name`` over ``n`` rows, made
    absent everywhere (length −1) on first use."""
    if name not in field_offs:
        field_offs[name] = np.zeros(n, dtype=np.int32)
        field_lens[name] = np.full(n, -1, dtype=np.int32)
    return field_offs[name], field_lens[name]


class ProcessorParseJson(Processor):
    name = "processor_parse_json_tpu"
    supports_columnar = True

    def __init__(self) -> None:
        super().__init__()
        self.source_key = b"content"
        self.keep_source_on_fail = True
        self.keep_source_on_success = False
        self.renamed_source_key = RAW_LOG_KEY
        self._pipeline = ""
        #: key signature (ops/kernels/json_fields.py) → key names
        self._sig_names: Dict[int, list] = {}

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        super().init(config, context)
        self.source_key = config.get("SourceKey", "content").encode()
        self.keep_source_on_fail = bool(config.get("KeepingSourceWhenParseFail", True))
        self.keep_source_on_success = bool(config.get("KeepingSourceWhenParseSucceed", False))
        self.renamed_source_key = config.get("RenamedSourceKey", RAW_LOG_KEY)
        self._pipeline = getattr(context, "pipeline_name", "") or ""
        return True

    def process(self, group: PipelineEventGroup) -> None:
        src = extract_source(group, self.source_key)
        if src is None:
            return
        n = len(src.offsets)
        if src.columnar:
            cols = group.columns
            ok = np.zeros(n, dtype=bool)
            field_offs: Dict[str, np.ndarray] = {}
            field_lens: Dict[str, np.ndarray] = {}
            raw = src.arena

            todo = np.nonzero(src.present)[0]
            keys = self._discover_schema(raw, src, todo)
            handled = False
            drift_rows = 0
            if keys is not None:
                handled, drift_rows = self._process_struct(
                    group, src, raw, keys, ok, field_offs, field_lens)
            if not handled and keys is not None:
                # the structural plane declined (native unavailable): one
                # stable-schema native pass, everything else per row
                from .. import native as _native
                res = _native.json_extract(raw, src.offsets, src.lengths,
                                           keys)
                if res is not None:
                    f_offs, f_lens, c_ok, _ = res
                    c_ok = c_ok & src.present
                    for fi, k in enumerate(keys):
                        name = k.decode("utf-8", "replace")
                        field_offs[name] = f_offs[fi].copy()
                        field_lens[name] = np.where(c_ok, f_lens[fi], -1)
                    ok |= c_ok
            todo = np.nonzero(src.present & ~ok)[0]
            self._fallback_rows(group, src, raw, todo, ok,
                                field_offs, field_lens, count=handled,
                                drift_rows=drift_rows)
            self._install(cols, src, ok, field_offs, field_lens)
            return

        self._process_rows(group)

    # -- the fused run's device stage -----------------------------------------

    def fused_stage_spec(self, ctx):
        """loongresident: beside a fusable neighbour (a filter on a parsed
        key) the parse joins the fused pipeline program as a
        ``json_fields`` stage (ops/kernels/json_fields.py): the device
        turns the packed rows into the value spans of their top-level
        members, publishes them as span columns, and mints a named capture
        for every key a later member binds.  Refused — the pipeline keeps
        the host plane — when a processor ahead of the run may have minted
        the fields a later member means, or when parsed rows also keep
        their source (a filter on the renamed source key would then mean
        the raw line, not a JSON member)."""
        if ctx.user_stages_ahead or self.keep_source_on_success:
            return None
        if not ctx.bind_source(self.source_key):
            return None
        from ..ops import fused_pipeline as fp
        from ..ops.kernels.json_fields import (DMAX, JsonFieldsKernel,
                                               JsonFieldsPlan)
        from ..pipeline.fused_chain import FusedMemberStage
        plan = JsonFieldsPlan()
        spec = fp.StageSpec("json_fields", plan,
                            ["json_fields", plan.kmax, DMAX, plan.bound],
                            staged=JsonFieldsKernel(plan),
                            label=f"json_fields:{self.name}")
        ctx.note_open_fields(ctx.n_stages, plan.bind)
        ctx.note_consumed(self.source_key)
        return FusedMemberStage(spec, self._fused_apply)

    def _fused_apply(self, group, src, out, rowmap):
        """Host epilogue of the ``json_fields`` stage: install the device's
        spans as columns (key names decoded once per distinct signature),
        hand every row the device did not prove — whole — to the native
        emitter (`_process_struct` on that subset, then the counted
        ``json.loads`` tier), keep ``rawLog`` for rows that do not parse,
        exactly as `process` does.  The rows whose spans the device did
        not produce are left on ``src.undecided`` for a later keep member:
        the device's mask says nothing of them."""
        from .. import trace
        from ..ops.fused_pipeline import note_json_rows
        from ..ops.kernels.json_fields import STATUS_NAMES, STATUS_SHAPE
        from .common import subset_source
        whole = len(rowmap) == len(out[0])       # no member compacted yet
        dev_ok, off, ln, status, members, sig = \
            out if whole else (a[rowmap] for a in out)
        sub = subset_source(src, rowmap)
        n = len(rowmap)
        raw = sub.arena
        cols = group.columns
        dev_ok = dev_ok & sub.present
        status = status.copy()
        field_offs: Dict[str, np.ndarray] = {}
        field_lens: Dict[str, np.ndarray] = {}
        sig64 = (sig[:, 0].astype(np.int64) << 32) \
            | (sig[:, 1].astype(np.int64) & 0xFFFFFFFF)
        decoded = 0
        major_names: list = []
        uniq, counts = np.unique(sig64[dev_ok], return_counts=True)
        for u in uniq[np.argsort(-counts)]:
            mask = dev_ok & (sig64 == u)
            rows = np.nonzero(mask)[0]
            names = self._sig_names.get(int(u))
            if names is None:
                decoded += 1
                names = self._decode_names(raw, sub, int(rows[0]), int(u))
            if names is None or len(names) != int(members[rows[0]]):
                # the names do not fit the members the device counted:
                # nothing is guessed, the host's emitter takes the rows
                dev_ok[rows] = False
                status[rows] = STATUS_SHAPE
                continue
            if not major_names:
                # the group's commonest schema: its columns are the
                # stage's own, transposed once; rows of another schema are
                # absent from them until their own pass below
                major_names = names
                m = len(names)
                off_t = np.ascontiguousarray(off[:, :m].T)
                ln_t = np.where(mask[None, :], ln[:, :m].T, np.int32(-1))
            for k, name in enumerate(names):
                if names is major_names and name not in field_offs:
                    field_offs[name], field_lens[name] = off_t[k], ln_t[k]
                    continue
                fo, fl = _column(field_offs, field_lens, name, n)
                fo[rows] = off[rows, k]
                fl[rows] = ln[rows, k]
        hrows = np.nonzero(sub.present & ~dev_ok)[0]
        ok = dev_ok
        handled, drift_rows = True, 0
        if len(hrows):
            tracer = trace.active_tracer()
            if tracer is not None:
                t0 = time.perf_counter()
                cpu0 = time.thread_time()
            handled, drift_rows = self._process_struct(
                group, sub, raw, [k.encode("utf-8") for k in major_names],
                ok, field_offs, field_lens, rows=hrows)
            if tracer is not None:
                cpu_s = time.thread_time() - cpu0
                tracer.record_timed("processor", "json.host_emit", t0,
                                    time.perf_counter() - t0,
                                    {"rows": int(len(hrows))}, cpu_s)
        by_reason = np.bincount(status[hrows], minlength=len(STATUS_NAMES))
        note_json_rows(int(sub.present.sum()),
                       {STATUS_NAMES[i]: int(by_reason[i])
                        for i in range(1, len(STATUS_NAMES))}, decoded)
        self._fallback_rows(group, sub, raw, hrows[~ok[hrows]], ok,
                            field_offs, field_lens, count=handled,
                            drift_rows=drift_rows)
        self._install(cols, sub, ok, field_offs, field_lens)
        undecided = np.zeros(len(src.offsets), dtype=bool)
        undecided[rowmap[hrows]] = True
        src.undecided = undecided if len(hrows) else None
        return rowmap

    def _decode_names(self, raw, src, i: int, signature: int):
        """The key names of row ``i``, in member order (a name twice stays
        twice: the later member overwrites the earlier, as ``json.loads``
        has it), remembered under the row's key signature."""
        o, ln = int(src.offsets[i]), int(src.lengths[i])
        try:
            # once per distinct key signature, not per row
            # loonglint: disable=per-row-parse
            names = json.loads(raw[o:o + ln].tobytes(),
                               object_pairs_hook=lambda kv: [k for k, _ in kv])
        except ValueError:
            return None
        if len(self._sig_names) >= 4096:
            self._sig_names.clear()
        self._sig_names[signature] = names
        return names

    def _install(self, cols, src, ok, field_offs, field_lens) -> None:
        """The parsed fields into the group's columns, the source consumed
        or retained as the keep flags say."""
        for k in field_offs:
            cols.set_field(k, field_offs[k], field_lens[k])
        if not src.from_content:
            from .common import consume_named_source
            consume_named_source(cols, self.source_key, set(field_offs))
        self._retain_source(cols, src, ok)
        cols.parse_ok = ok
        if src.from_content:
            cols.content_consumed = True

    # -- structural-index plane --------------------------------------------

    def _process_struct(self, group, src, raw, keys, ok,
                        field_offs, field_lens, rows=None) -> bool:
        """Columnar parse via lct_json_struct_parse, of every row or of the
        subset ``rows`` (indices; the rows a fused run's device stage handed
        back).  Returns (handled, drift_row_count); handled False when the
        native plane is unavailable (caller uses the r09-style path, or the
        per-row tier for a subset).  On success, `ok`/field dicts hold
        every row parsed here; the counted per-row fallbacks stay False in
        `ok`."""
        from .. import native as _native
        offsets, lengths, present = src.offsets, src.lengths, src.present
        if rows is not None:
            offsets, lengths, present = \
                offsets[rows], lengths[rows], present[rows]
        res = _native.json_struct_parse(raw, offsets, lengths, keys)
        if res is None:
            return False, 0
        f_offs, f_lens, status, side, extras = res
        arena_len = len(raw)
        n = len(src.offsets)
        sb = group.source_buffer

        # one side-arena append per group: decoded escape bytes land in the
        # source buffer ONCE; side-sentinel offsets rebase vectorised
        from .common import append_side_arena, rebase_side_spans
        rebase = append_side_arena(sb, side, arena_len)
        c_ok = (status != 1) & present
        all_ok = bool(c_ok.all())
        for fi, k in enumerate(keys):
            name = k.decode("utf-8", "replace")
            lens_f = f_lens[fi]
            offs_f = rebase_side_spans(f_offs[fi], lens_f, arena_len,
                                       rebase)
            # steady state (every row parsed): install the kernel columns
            # as-is instead of re-masking them per field
            lens_f = lens_f if all_ok else np.where(c_ok, lens_f, -1)
            if rows is None:
                field_offs[name], field_lens[name] = offs_f, lens_f
            else:
                fo, fl = _column(field_offs, field_lens, name, n)
                fo[rows], fl[rows] = offs_f, lens_f
        # schema drift: unknown keys arrive as a CSR extras stream of raw
        # spans — installed as columns without touching json.loads
        e_rows, e_koffs, e_klens, e_voffs, e_vlens = extras
        for j in range(len(e_rows)):
            i = int(e_rows[j])
            kb = raw[int(e_koffs[j]): int(e_koffs[j]) + int(e_klens[j])]
            name = kb.tobytes().decode("utf-8", "replace")
            fo, fl = _column(field_offs, field_lens, name, n)
            vo = int(e_voffs[j])
            if vo >= arena_len:
                vo += rebase
            if rows is not None:
                i = int(rows[i])
            fo[i] = vo
            fl[i] = int(e_vlens[j])
        if rows is None:
            ok |= c_ok
        else:
            ok[rows] |= c_ok
        return True, int((status == 2).sum())

    def _fallback_rows(self, group, src, raw, todo, ok,
                       field_offs, field_lens, count: bool,
                       drift_rows: int = 0) -> None:
        """Per-row json.loads for rows the index could not prove
        well-formed.  The ONLY per-row Python left on this processor —
        counted, and alarmed when sustained."""
        n = len(src.offsets)
        sb = group.source_buffer
        n_fallback = 0
        for i in todo:
            n_fallback += 1
            o, ln = int(src.offsets[i]), int(src.lengths[i])
            try:
                # the counted fallback tier the structural plane demotes
                # malformed rows to (parse_fallback_rows_total)
                # loonglint: disable=per-row-parse
                obj = json.loads(raw[o : o + ln].tobytes())
                if not isinstance(obj, dict):
                    raise ValueError
            except Exception:  # noqa: BLE001
                continue
            ok[i] = True
            for k, v in obj.items():
                fo, fl = _column(field_offs, field_lens, k, n)
                if isinstance(v, str):
                    vb = v.encode("utf-8")
                elif isinstance(v, (dict, list)):
                    vb = json.dumps(v, ensure_ascii=False).encode("utf-8")
                elif isinstance(v, bool):
                    vb = b"true" if v else b"false"
                elif v is None:
                    vb = b"null"
                else:
                    vb = str(v).encode("utf-8")
                view = sb.copy_string(vb)
                fo[i] = view.offset
                fl[i] = view.length
        if count:
            from . import parse_telemetry
            parse_telemetry.note_rows(self.name, self._pipeline,
                                      int(src.present.sum()), n_fallback,
                                      drift=drift_rows)

    # -- row path -----------------------------------------------------------

    def _process_rows(self, group: PipelineEventGroup) -> None:
        # row path keep/discard: the shared reference ordering (capture
        # raw, delete unless overwritten, re-add under the renamed key)
        from .common import finish_row_keep
        sb = group.source_buffer
        renamed = self.renamed_source_key.encode()
        for ev in group.events:
            if not hasattr(ev, "get_content"):
                continue
            raw = ev.get_content(self.source_key)
            if raw is None:
                continue
            try:
                # non-columnar groups (per-event plugins upstream) have no
                # arena to index
                # loonglint: disable=per-row-parse
                obj = json.loads(raw.to_bytes())
                if not isinstance(obj, dict):
                    raise ValueError
            except Exception:  # noqa: BLE001
                finish_row_keep(ev, raw, False, self.source_key, False,
                                self.keep_source_on_fail,
                                self.keep_source_on_success, renamed)
                continue
            overwritten = False
            for k, val in obj.items():
                if not isinstance(val, str):
                    val = json.dumps(val, ensure_ascii=False) \
                        if isinstance(val, (dict, list)) else \
                        ("true" if val is True else "false" if val is False
                         else "null" if val is None else str(val))
                kb = k.encode() if isinstance(k, str) else k
                ev.set_content(sb.copy_string(kb), sb.copy_string(val))
                if kb == self.source_key:
                    overwritten = True
            finish_row_keep(ev, raw, True, self.source_key, overwritten,
                            self.keep_source_on_fail,
                            self.keep_source_on_success, renamed)

    @staticmethod
    def _discover_schema(raw, src, candidates):
        for i in candidates[:4]:
            o, ln = int(src.offsets[i]), int(src.lengths[i])
            try:
                # bounded schema probe (<= 4 rows per group), not a tail
                # loonglint: disable=per-row-parse
                obj = json.loads(raw[o : o + ln].tobytes())
            except ValueError:
                continue
            if isinstance(obj, dict) and obj and len(obj) <= 128:
                return [k.encode("utf-8") for k in obj.keys()]
        return None

    def _retain_source(self, cols: ColumnarLogs, src, ok: np.ndarray) -> None:
        if self.keep_source_on_fail and self.keep_source_on_success:
            keep = src.present
        elif self.keep_source_on_fail:
            keep = (~ok) & src.present
        elif self.keep_source_on_success:
            keep = ok & src.present
        else:
            keep = np.zeros(len(ok), dtype=bool)
        if keep.any():
            cols.set_field(self.renamed_source_key,
                           src.offsets.astype(np.int32),
                           np.where(keep, src.lengths, -1).astype(np.int32))
