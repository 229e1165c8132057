"""Inner processor: multiline log assembly (stacktrace merging) — columnar.

Reference: core/plugin/processor/inner/ProcessorSplitMultilineLogStringNative
.cpp with MultilineOptions (file_server/MultilineOptions.h:38-47):
start/continue/end regexes group physical lines into logical events;
UnmatchedContentTreatment = discard | single_line.

TPU-first: this is the framework's "long-context" problem (SURVEY.md §5.7).
With a StartPattern, line classification runs as ONE device match batch, and
because split lines are contiguous slices of the same arena, merging a block
of lines is pure span arithmetic — the merged event is the arena span from
the first line's offset to the last line's end, newlines included, zero-copy.
Continue/End patterns run the same batched classification with a host-side
block-boundary pass.

Two legs (PR 31): a StartPattern alone on the SEGMENT tier — upstream's
documented Java-stacktrace deployment — classifies through the async device
plane.  ``process_dispatch`` sends the group's physical lines through the one
dispatch window (``RegexEngine.match_batch_async``: the match gate, a jit
family of its own, one result word a row) and returns; ``process_complete``
takes the booleans and runs the block walk, the carry stitching and the emit
below, unchanged.  The worker fills the round trip with its neighbours'
stages (``Pipeline._walk_chain``).  Every other mode (End / Continue
patterns, the fused classify set, a DFA- or CPU-tier pattern) classifies
inside the dispatch leg as before and leaves nothing in flight.

Cross-chunk carry: the file reader holds open records in the file (its
multiline rollback), so chunks normally start and end on record boundaries.
When it CANNOT hold (record longer than a chunk, flush timeout) it marks
the group ML_PARTIAL_TAIL and the follow-up ML_CONTINUE; this processor
then stashes the open record's bytes per source and stitches them onto the
next chunk's leading lines, so a stacktrace split mid-record across two
read chunks still yields ONE event (round-2 VERDICT item 3).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..models import ColumnarLogs, EventGroupMetaKey, PipelineEventGroup
from ..ops.regex.engine import RegexEngine, get_engine
from ..ops.regex.program import PatternTier
from ..pipeline.plugin.interface import PluginContext, Processor
from .common import stage_span

CARRY_CAP_BYTES = 1 << 20   # give up stitching records larger than this
CARRY_FLUSH_S = 5.0         # idle carries flush via the pipeline timeout tick
CARRY_TTL_S = 30.0          # orphaned stashes flush through the next group

# /debug/status ``multiline``: per pipeline, cumulative for the process
COUNTERS = ("lines_total", "records_total", "device_lines_total",
            "host_lines_total", "unmatched_lines_total",
            "carry_stitched_total", "carry_flushed_total",
            "carry_oversize_total")
_stats_lock = threading.Lock()
_stats: Dict[str, Dict[str, int]] = {}
_calls: Dict[str, Dict[str, int]] = {}


def _note(pipeline: str, **deltas: int) -> None:
    with _stats_lock:
        row = _stats.get(pipeline)
        if row is None:
            row = _stats[pipeline] = dict.fromkeys(COUNTERS, 0)
        for k, v in deltas.items():
            row[k] += v


def _note_calls(pipeline: str, shapes) -> None:
    with _stats_lock:
        calls = _calls.setdefault(pipeline, {})
        for rows, width in shapes:
            key = f"{rows}x{width}"
            calls[key] = calls.get(key, 0) + 1


def status() -> Dict[str, dict]:
    """The ``multiline`` section of /debug/status: by pipeline, physical
    lines in and records out, lines classified on the device and on the
    host (native walker, ``re``, the fused set's host scan), unmatched
    lines shipped or discarded, what became of held open records —
    stitched onto the next chunk, flushed alone (timeout tick, stop drain,
    orphan TTL), or too large to hold — and ``classify_calls``: the calls
    of the async leg's match gate by geometry (``<rows>x<width>``)."""
    with _stats_lock:
        return {k: dict(v, classify_calls=dict(_calls.get(k, {})))
                for k, v in _stats.items()}


def reset_for_testing() -> None:
    with _stats_lock:
        _stats.clear()
        _calls.clear()


class ProcessorSplitMultilineLogString(Processor):
    name = "processor_split_multiline_log_string_native"
    supports_columnar = True
    requires_columnar = True
    supports_async_dispatch = True

    def __init__(self) -> None:
        super().__init__()
        self._pipeline = ""
        self._async_start = False
        self.start: Optional[RegexEngine] = None
        self.cont: Optional[RegexEngine] = None
        self.end: Optional[RegexEngine] = None
        self.unmatched = "single_line"  # or "discard"
        # per-source open-record stash: key → (bytes, event_ts, stashed_at);
        # locked: _finish runs on processor threads, flush_timeout_groups on
        # thread 0's timeout tick (same contract as Batcher)
        self._carry: Dict[str, Tuple[bytes, int, float]] = {}
        self._carry_lock = threading.Lock()

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        super().init(config, context)
        mcfg = config.get("Multiline", config)
        sp = mcfg.get("StartPattern")
        cp = mcfg.get("ContinuePattern")
        ep = mcfg.get("EndPattern")
        self.start = get_engine(self._fullmatchify(sp)) if sp else None
        self.cont = get_engine(self._fullmatchify(cp)) if cp else None
        self.end = get_engine(self._fullmatchify(ep)) if ep else None
        self.unmatched = mcfg.get("UnmatchedContentTreatment", "single_line")
        # loongfuse: classify start/continue/end in ONE scan (one device
        # pass / one native table walk) instead of a match batch per
        # pattern — the per-pattern round trips are what collapsed
        # multiline on TPU (1.6 MB/s, ROADMAP item 3)
        self._fused_set = None
        self._fused_slots: Dict[str, int] = {}
        pats = [(name, eng.pattern) for name, eng in
                (("start", self.start), ("cont", self.cont),
                 ("end", self.end)) if eng is not None]
        if len(pats) > 1:
            from ..ops.regex.fuse import try_build_set
            self._fused_set = try_build_set([p for _, p in pats],
                                            names=[n for n, _ in pats])
            if self._fused_set is not None:
                self._fused_slots = {n: i for i, (n, _) in enumerate(pats)}
        self._pipeline = getattr(context, "pipeline_name", "") or ""
        # the async leg: a StartPattern alone whose program the device
        # walks (module docstring); read once, from what init compiled
        self._async_start = (
            self.start is not None and self.cont is None
            and self.end is None
            and self.start.tier is PatternTier.SEGMENT
            and self.start._segment_kernel is not None)
        return self.start is not None or self.end is not None

    @staticmethod
    def _classify(masks, name, engine, arena, offs, lens) -> np.ndarray:
        """Fused classification when the pattern joined the set; the
        per-pattern match batch when it was demoted or the set didn't
        fuse — identical booleans either way."""
        got = masks.get(name)
        if got is not None:
            return got
        return engine.match_batch(arena, offs, lens)

    @staticmethod
    def _fullmatchify(pattern: str) -> str:
        """Reference multiline patterns are full-line matches; users commonly
        write prefixes ending in `.*` — keep as-is (engine is full-match)."""
        return pattern

    def process(self, group: PipelineEventGroup) -> None:
        self.process_complete(group, self.process_dispatch(group))

    def process_dispatch(self, group: PipelineEventGroup):
        """Classify the group's lines.  On the async leg the match gate's
        chunks stay in flight and the token carries them; everywhere else
        the whole stage runs here and the token is None."""
        cols = group.columns
        if cols is None or group._events:
            return None  # expects the line-split columnar form
        n = len(cols)
        if n == 0:
            return None
        arena = group.source_buffer.as_array()
        offs = cols.offsets.astype(np.int64)
        lens = cols.lengths

        if self._async_start:
            with stage_span("multiline.classify.dispatch"):
                pending = self.start.match_batch_async(arena, offs, lens)
            on_host = n if pending.done else len(pending.cpu_idx)
            _note(self._pipeline, lines_total=n, host_lines_total=on_host,
                  device_lines_total=n - on_host)
            if not pending.done:
                return cols, arena, offs, lens, pending
            self._merge(group, cols, arena, offs, lens,
                        {"start": pending.result().ok})
            return None

        masks: Dict[str, Optional[np.ndarray]] = {}
        with stage_span("multiline.classify"):
            if self._fused_set is not None:
                member = self._fused_set.member_masks(
                    self._fused_set.classify(arena, offs, lens))
                masks = {name: member[slot]
                         for name, slot in self._fused_slots.items()}
            for name, eng in (("start", self.start), ("end", self.end),
                              ("cont", self.cont)):
                if eng is not None and masks.get(name) is None:
                    masks[name] = eng.match_batch(arena, offs, lens)
        _note(self._pipeline, lines_total=n, host_lines_total=n)
        self._merge(group, cols, arena, offs, lens, masks)
        return None

    def process_complete(self, group: PipelineEventGroup, token) -> None:
        if token is None:
            return
        cols, arena, offs, lens, pending = token
        with stage_span("multiline.classify.complete"):
            is_start = pending.result().ok
        _note_calls(self._pipeline, pending.calls)
        self._merge(group, cols, arena, offs, lens, {"start": is_start})

    def _merge(self, group, cols, arena, offs, lens, masks) -> None:
        """Block walk, carry stitching and emit over classified lines."""
        with stage_span("multiline.merge"):
            self._classify_blocks(group, cols, arena, offs, lens, masks)
        _note(self._pipeline, records_total=len(group.columns))

    def fused_stage_spec(self, ctx):
        """loongresident: the start/continue/end classify scan joins a
        fused pipeline program as its LAST stage (``terminal=True`` — the
        block merge rebuilds the row population, so nothing downstream
        can consume the packed rows).  The block walk and carry stitching
        are unchanged host logic over the scan's tag bitmask."""
        fs = self._fused_set
        if fs is None or not fs.fdfa.device_ok:
            return None
        if not ctx.bind_source(b"content"):
            return None
        from ..ops import fused_pipeline as fp
        from ..pipeline.fused_chain import FusedMemberStage
        spec = fp.StageSpec("scan", fs.fdfa,
                            ["scan"] + list(fs.fdfa.patterns),
                            staged=fs._device_kernel(),
                            terminal=True, label="multiline-classify")
        return FusedMemberStage(spec, self._fused_apply)

    def _fused_apply(self, group, src, out, rowmap):
        cols = group.columns
        if cols is None or group._events or len(rowmap) != len(cols):
            return rowmap
        arena = group.source_buffer.as_array()
        tags = np.asarray(out[0]).astype(np.uint32)[rowmap]
        member = self._fused_set.member_masks(tags)
        masks = {name: member[slot]
                 for name, slot in self._fused_slots.items()}
        _note(self._pipeline, lines_total=len(cols),
              device_lines_total=len(cols))
        self._merge(group, cols, arena, cols.offsets.astype(np.int64),
                    cols.lengths, masks)
        return rowmap

    def _classify_blocks(self, group, cols, arena, offs, lens,
                         masks: Dict[str, Optional[np.ndarray]]) -> None:
        n = len(cols)
        is_start = (self._classify(masks, "start", self.start, arena, offs,
                                   lens)
                    if self.start else np.zeros(n, dtype=bool))
        is_end = (self._classify(masks, "end", self.end, arena, offs, lens)
                  if self.end else None)
        is_cont = (self._classify(masks, "cont", self.cont, arena, offs,
                                  lens)
                   if self.cont else None)

        # blocks as parallel arrays (first[k], last[k]) + sorted unmatched
        # indices — vectorised in the hot modes (start-only, end-only);
        # start+end / start+cont have a sequential absorb dependency and
        # walk Python lists
        if self.start is not None:
            starts_idx = np.nonzero(is_start)[0]
            if is_end is not None or is_cont is not None:
                first, last, unmatched = self._walk_blocks(
                    n, is_start.tolist(),
                    is_end.tolist() if is_end is not None else None,
                    is_cont.tolist() if is_cont is not None else None)
            else:
                # start-only: block k spans starts_idx[k] ..
                # (starts_idx[k+1] - 1); leading lines are unmatched
                if len(starts_idx):
                    first = starts_idx.astype(np.int64)
                    last = np.concatenate([starts_idx[1:] - 1, [n - 1]])
                    unmatched = np.arange(int(starts_idx[0]), dtype=np.int64)
                else:
                    first = np.zeros(0, dtype=np.int64)
                    last = np.zeros(0, dtype=np.int64)
                    unmatched = np.arange(n, dtype=np.int64)
        else:
            # end-only mode: block closes at each end-match
            ends_idx = np.nonzero(is_end)[0].astype(np.int64)
            if len(ends_idx):
                last = ends_idx
                first = np.concatenate([[0], ends_idx[:-1] + 1])
                tail_start = int(ends_idx[-1]) + 1
            else:
                first = last = np.zeros(0, dtype=np.int64)
                tail_start = 0
            unmatched = np.arange(tail_start, n, dtype=np.int64)

        self._finish(group, cols, arena, first, last, unmatched, is_end)

    @staticmethod
    def _walk_blocks(n, s_l, e_l, c_l):
        """start+end / start+cont block walk (sequential absorb dependency:
        a start line inside an open block is consumed by it, so this cannot
        vectorise).  end mode closes at an end-match; cont mode extends
        while the NEXT line continues."""
        firsts: List[int] = []
        lasts: List[int] = []
        unmatched_l: List[int] = []
        i = 0
        while i < n:
            if s_l[i]:
                j = i
                if e_l is not None:
                    while j < n and not e_l[j]:
                        j += 1
                    if j >= n:
                        j = n - 1
                else:
                    while j + 1 < n and c_l[j + 1]:
                        j += 1
                firsts.append(i)
                lasts.append(j)
                i = j + 1
            else:
                unmatched_l.append(i)
                i += 1
        return (np.array(firsts, dtype=np.int64),
                np.array(lasts, dtype=np.int64),
                np.array(unmatched_l, dtype=np.int64))

    # -- carry stitching + emission -----------------------------------------

    def _source_key(self, group: PipelineEventGroup) -> str:
        path = group.get_metadata(EventGroupMetaKey.LOG_FILE_PATH) or ""
        ino = group.get_metadata(EventGroupMetaKey.LOG_FILE_INODE) or ""
        return f"{path}:{ino}"

    def _finish(self, group, cols, arena, first, last, unmatched,
                is_end) -> None:
        n = len(cols)
        offs = cols.offsets.astype(np.int64)
        lens = cols.lengths.astype(np.int64)
        tss = cols.timestamps
        key = self._source_key(group)
        ml_continue = group.get_metadata(EventGroupMetaKey.ML_CONTINUE) == "1"
        ml_partial = group.get_metadata(
            EventGroupMetaKey.ML_PARTIAL_TAIL) == "1"
        with self._carry_lock:
            carried = self._carry.pop(key, None)

        # injected: (order, bytes, ts) — carried records copied into the
        # group's arena at emit time (offset-stable across buffer growth)
        injected: List[Tuple[int, bytes, int]] = []

        # expire orphaned stashes (source rotated/deleted and never came
        # back): deliver their bytes through THIS group rather than losing
        # them — content intact, group-level source meta may differ
        now = time.monotonic()
        with self._carry_lock:
            for k in list(self._carry):
                b, t, at = self._carry[k]
                if now - at > CARRY_TTL_S:
                    del self._carry[k]
                    injected.append((-2, b, t))
                    _note(self._pipeline, carry_flushed_total=1)

        # leading run of unmatched lines (contiguous from line 0) — the
        # lines a carried open record can continue into
        m = len(unmatched)
        brk = np.nonzero(unmatched != np.arange(m))[0]
        lead_end = int(brk[0]) if len(brk) else m

        lead_consumed = 0
        if carried is not None:
            cbytes, cts, _ = carried
            take = 0               # leading lines absorbed into the carry
            closed = False         # the absorbed run CLOSES the record
            if ml_continue:
                if self.end is not None and self.start is None:
                    # end-only mode: continuation lines close at an
                    # end-match and therefore form blocks[0], not unmatched
                    if len(first) and first[0] == 0:
                        take = int(last[0]) + 1
                        first, last = first[1:], last[1:]
                        closed = True
                    elif not len(first) and lead_end == n:
                        take = n   # no END yet: whole chunk continues
                else:
                    # start modes: absorb the leading unmatched run, but in
                    # start+end mode STOP at the first end-match — lines
                    # after it are ordinary unmatched content
                    take = lead_end
                    if is_end is not None:
                        hits = np.nonzero(is_end[:lead_end])[0]
                        if len(hits):
                            take = int(hits[0]) + 1
                            closed = True
            if take > 0:
                span_lo = int(offs[0])
                span_hi = int(offs[take - 1] + lens[take - 1])
                # line spans exclude their trailing newline, so the joint
                # between the carried half and this chunk needs it back
                merged = cbytes + b"\n" + bytes(
                    arena[span_lo:span_hi].tobytes())
                lead_consumed = take
                if ml_partial and not closed and take == n and not len(first):
                    # the whole chunk is still the SAME open record —
                    # keep carrying (unless it outgrew the cap)
                    self._stash(key, merged, cts, injected)
                else:
                    injected.append((-1, merged, cts))
                    _note(self._pipeline, carry_stitched_total=1)
            else:
                # record ended exactly at the chunk boundary (next line is a
                # start) or the continuation never arrived: emit standalone
                injected.append((-1, cbytes, cts))
                _note(self._pipeline, carry_stitched_total=1)

        # tail record to stash when this chunk breaks mid-record (skip when
        # the whole chunk was already re-stashed as the carried record)
        if ml_partial and lead_consumed < n:
            if len(last) and last[-1] == n - 1:
                f_, l_ = int(first[-1]), int(last[-1])
                first, last = first[:-1], last[:-1]
                lo = int(offs[f_])
                hi = int(offs[l_] + lens[l_])
                self._stash(key, bytes(arena[lo:hi].tobytes()),
                            int(tss[f_]), injected)
            else:
                # trailing contiguous unmatched run ending at the last line
                # continues an open record
                m = len(unmatched)
                rev_brk = np.nonzero(
                    unmatched[::-1] != (n - 1 - np.arange(m)))[0]
                run = int(rev_brk[0]) if len(rev_brk) else m
                run = min(run, n - lead_consumed)
                if run > 0:
                    tail_run = unmatched[m - run:]
                    unmatched = unmatched[:m - run]
                    lo = int(offs[tail_run[0]])
                    hi = int(offs[tail_run[-1]] + lens[tail_run[-1]])
                    self._stash(key, bytes(arena[lo:hi].tobytes()),
                                int(tss[tail_run[0]]), injected)

        kept = unmatched[unmatched >= lead_consumed]
        if len(kept):
            _note(self._pipeline, unmatched_lines_total=len(kept))
        if self.unmatched == "discard":
            kept = np.zeros(0, dtype=np.int64)
        # records, vectorised: blocks are [offs[first], offs[last]+lens[last])
        # spans (newlines included — contiguous arena slices), unmatched
        # lines are their own spans; `order` (the block's first line index)
        # restores input order
        rec_order = np.concatenate([first, kept])
        rec_off = np.concatenate([offs[first], offs[kept]])
        rec_len = np.concatenate(
            [offs[last] + lens[last] - offs[first], lens[kept]])
        rec_ts = (tss[rec_order] if tss is not None
                  else np.zeros(len(rec_order), dtype=np.int64))
        self._emit(group, rec_order, rec_off, rec_len, rec_ts, injected)

    def _stash(self, key, data: bytes, ts: int, injected) -> None:
        if len(data) > CARRY_CAP_BYTES:
            injected.append((1 << 30, data, ts))  # too big: emit as-is, last
            _note(self._pipeline, carry_oversize_total=1)
            return
        with self._carry_lock:
            prev = self._carry.pop(key, None)
            self._carry[key] = (data, ts, time.monotonic())
        if prev is not None:
            # With multiple processor threads, chunks of one source can be
            # processed out of order: a concurrent worker stashed for this
            # key between our pop and this stash. Overwriting would LOSE
            # that open record — emit it standalone instead (degraded
            # stitching, zero loss).
            injected.append((-3, prev[0], prev[1]))

    # -- pipeline drain hooks (idle/shutdown delivery of held records) ------

    def _carry_group(self, key: str, data: bytes,
                     ts: int) -> PipelineEventGroup:
        from ..models import SourceBuffer
        sb = SourceBuffer(len(data) + 64)
        g = PipelineEventGroup(sb)
        view = sb.copy_string(data)
        g.set_columns(ColumnarLogs(
            offsets=np.array([view.offset], np.int32),
            lengths=np.array([len(data)], np.int32),
            timestamps=np.array([ts or int(time.time())], np.int64)))
        path, _, ino = key.rpartition(":")
        if path:
            g.set_metadata(EventGroupMetaKey.LOG_FILE_PATH, path)
        if ino:
            g.set_metadata(EventGroupMetaKey.LOG_FILE_INODE, ino)
        return g

    def flush_timeout_groups(self) -> List[PipelineEventGroup]:
        """Carried records whose continuation never arrived flush on the
        pipeline's timeout tick, so an idle source still delivers its last
        record (reference flush-timeout semantics)."""
        now = time.monotonic()
        expired: List[Tuple[str, bytes, int]] = []
        with self._carry_lock:
            for key in list(self._carry):
                data, ts, at = self._carry[key]
                if now - at >= CARRY_FLUSH_S:
                    del self._carry[key]
                    expired.append((key, data, ts))
        if expired:
            _note(self._pipeline, carry_flushed_total=len(expired),
                  records_total=len(expired))
        return [self._carry_group(k, d, t) for k, d, t in expired]

    def drain_groups(self) -> List[PipelineEventGroup]:
        """Shutdown: every held record ships (pipeline stop drain)."""
        with self._carry_lock:
            held = list(self._carry.items())
            self._carry.clear()
        if held:
            _note(self._pipeline, carry_flushed_total=len(held),
                  records_total=len(held))
        return [self._carry_group(k, d, t) for k, (d, t, _) in held]

    def _emit(self, group, rec_order, rec_off, rec_len, rec_ts,
              injected) -> None:
        sb = group.source_buffer
        if injected:
            extra = []
            for order, data, ts in injected:
                view = sb.copy_string(data)
                extra.append((order, view.offset, len(data), ts))
            rec_order = np.concatenate(
                [rec_order, [r[0] for r in extra]])
            rec_off = np.concatenate([rec_off, [r[1] for r in extra]])
            rec_len = np.concatenate([rec_len, [r[2] for r in extra]])
            rec_ts = np.concatenate([rec_ts, [r[3] for r in extra]])
        idx = np.argsort(rec_order, kind="stable")
        group.set_columns(ColumnarLogs(
            offsets=rec_off[idx].astype(np.int32),
            lengths=rec_len[idx].astype(np.int32),
            timestamps=rec_ts[idx].astype(np.int64)))
