"""Batched field extraction: Tier-1 segment programs on device.

Replaces the reference's hottest loop — per-event boost::regex_match with
capture-group extraction (ProcessorParseRegexNative.cpp:186-253) — with a
fully vectorised computation over a [B, L] byte tensor.

TPU-first formulation: NO gathers and NO sequential scans.  Per-element
gathers (LUT lookups, take_along_axis) and lax.scan/cummin chains are
TPU-hostile; every data-dependent query in the cursor walk is instead a
masked reduction over the length axis, which XLA fuses into tight VPU
loops:

    membership   m_c[b,l]        interval compares (elementwise)
    greedy end   min_l { l : ¬m_c[b,l] ∧ l ≥ cur[b] }        (min-reduce)
    run count    Σ_l   { m_c[b,l] ∧ cur ≤ l < cur+n }        (sum-reduce)
    literal ok   any_l { l = cur[b] ∧ lit_c[b,l] }           (or-reduce)

with lit_c precomputed by statically-shifted compares.  Composite ops
(optional groups, alternation) evaluate their bodies vectorised over ALL
rows and COMMIT per-row with masks — the branchless analogue of leftmost
/ greedy-preference semantics.  Everything is static-shape, jit-compiled
once per (program, B, L) geometry; the batch builder quantises B and L into
buckets to avoid recompilation storms (SURVEY.md §7 hard parts).

All per-row state is kept as [B, 1] columns (keepdims reductions) rather
than [B] vectors: the layout maps directly onto the VPU's (sublane, lane)
vregs, which lets the SAME walk body serve as the Pallas kernel body
(field_extract_pallas.py) where a [bB, L] tile is VMEM-resident and every
program op reads it without another HBM pass.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..regex.program import (INF, Alt, CapEnd, CapStart, FixedSpan, Lit,
                             Optional_, SegmentProgram, Span)


def _membership(rows: jnp.ndarray, intervals, complement_intervals) -> jnp.ndarray:
    """bool [B, L] membership via the cheaper of (intervals, ~complement).

    The OR-chain is seeded from the first interval compare, NOT from a
    `jnp.zeros` constant: constant i1 seeds get a sublane-replicated Mosaic
    layout, and `or`-ing a replicated mask with a data-derived one hits an
    unsupported i1 relayout ("non-singleton dimension replicated in
    destination but not source") when the Pallas path compiles on a real
    TPU.  Every mask here must stay data-dependent."""
    negate = len(complement_intervals) < len(intervals)
    if negate:
        intervals = complement_intervals
    m = None
    for lo, hi in intervals:
        t = (rows == lo) if lo == hi else ((rows >= lo) & (rows <= hi))
        m = t if m is None else (m | t)
    if m is None:                     # empty class: never matches
        m = rows != rows
    return ~m if negate else m


class _WalkState:
    """Per-row cursor/match/capture state threaded through the emitter.
    Everything is a [B, 1] column; capture columns are concrete default
    vectors from the start (offset 0, length -1 = absent), so branch
    merging is a pure element-wise select.  `ok` is carried as i32 0/1,
    not bool: Mosaic legalizes `select` on i1 VALUES through an i8
    round-trip whose final `arith.trunci i8 -> i1` the TPU backend
    rejects — predicates stay i1, selected data stays i32."""

    __slots__ = ("cur", "ok", "cap_off", "cap_len", "cap_start")

    def __init__(self, cur, ok, ncaps, init_caps: bool = True):
        self.cur = cur
        self.ok = ok
        if init_caps:
            B = cur.shape[0]
            zero = jnp.zeros((B, 1), jnp.int32)
            absent = jnp.full((B, 1), -1, jnp.int32)
            self.cap_off = [zero] * ncaps
            self.cap_len = [absent] * ncaps
            self.cap_start = [zero] * ncaps
        else:
            self.cap_off = []
            self.cap_len = []
            self.cap_start = []

    def copy(self) -> "_WalkState":
        st = _WalkState(self.cur, self.ok, 0, init_caps=False)
        st.cap_off = list(self.cap_off)
        st.cap_len = list(self.cap_len)
        st.cap_start = list(self.cap_start)
        return st

    def select(self, mask, taken: "_WalkState", other: "_WalkState") -> None:
        """self := taken where mask else other (element-wise per row)."""
        self.cur = jnp.where(mask, taken.cur, other.cur)
        self.ok = jnp.where(mask, taken.ok, other.ok)
        self.cap_off = [jnp.where(mask, a, b)
                        for a, b in zip(taken.cap_off, other.cap_off)]
        self.cap_len = [jnp.where(mask, a, b)
                        for a, b in zip(taken.cap_len, other.cap_len)]
        self.cap_start = [jnp.where(mask, a, b)
                          for a, b in zip(taken.cap_start, other.cap_start)]


def _any_row(mask: jnp.ndarray) -> jnp.ndarray:
    """`jnp.any(mask, axis=1, keepdims=True)` expressed as an i32
    max-reduction.  Mosaic lowers a bool (i1) row reduction through an i8
    accumulator and then emits `arith.trunci i8 -> i1`, which the TPU
    backend rejects ("Unsupported target bitwidth for truncation").
    Reducing in i32 and comparing sidesteps the i8 path entirely; under
    plain XLA the two forms fuse identically."""
    return jnp.max(mask.astype(jnp.int32), axis=1, keepdims=True) != 0


def walk_masks(program: SegmentProgram):
    """Static analysis shared by both builders: which class masks and
    literal-shift masks the walk needs."""
    span_classes: set = set()
    count_classes: set = set()
    literals: set = set()

    def collect(ops, reverse=False):
        for op in ops:
            if isinstance(op, Span):
                span_classes.add(op.class_id)
            elif isinstance(op, FixedSpan):
                count_classes.add(op.class_id)
            elif isinstance(op, Lit):
                # reverse-walk literals are stored reversed; the lit_ok map
                # is keyed by the forward spelling (match starting at l)
                literals.add(op.data[::-1] if reverse else op.data)
            elif isinstance(op, Optional_):
                collect(op.body, reverse)
            elif isinstance(op, Alt):
                for b in op.branches:
                    collect(b, reverse)
    collect(list(program.ops))
    if program.suffix_ops:
        collect(list(program.suffix_ops), reverse=True)
    if program.mid_ops:
        collect(list(program.mid_ops))
    if program.pivot is not None:
        count_classes.add(program.pivot.class_id)
    if program.pivot2 is not None:
        count_classes.add(program.pivot2.class_id)
    return span_classes, count_classes, literals


def build_extract_core(program: SegmentProgram):
    """Returns core(rows u8 [B,L], lens i32 [B,1]) ->
    (ok bool [B,1], cap_off i32 [B,C], cap_len i32 [B,C]).

    Pure jnp on the block it is given — usable directly under jit (XLA
    fuses the per-op reductions) or as a Pallas kernel body (the [B, L]
    tile stays VMEM-resident across ALL ops)."""

    ncaps = max(program.num_caps, 1)
    intervals = [c.intervals() for c in program.classes]
    comp_intervals = [c.negated().intervals() for c in program.classes]
    top_ops = list(program.ops)
    suffix_ops = list(program.suffix_ops) if program.suffix_ops else None
    pivot = program.pivot
    pivot2 = program.pivot2
    mid_ops = list(program.mid_ops) if program.mid_ops else None
    mid_end_caps = list(program.mid_end_caps)
    split_caps = list(program.split_caps)
    span_classes, count_classes, literals = walk_masks(program)
    if mid_ops is not None:
        mid_lit = next(op for op in mid_ops if isinstance(op, Lit))
        mid_fixed = len(mid_lit.data)

    def core(rows: jnp.ndarray, lens: jnp.ndarray):
        B, L = rows.shape
        i32 = jnp.int32
        # 2D iota: required inside Pallas/Mosaic, equivalent under XLA
        L32 = jnp.int32(L)
        # iota along lanes is row-constant, so Mosaic gives it a
        # sublane-REPLICATED layout; selects like `where(mask, pos, _)`
        # then try to relayout the i1 mask normal→replicated, which the
        # TPU backend rejects ("replicated in destination but not in
        # source").  Adding a data-dependent [B,1] zero column
        # de-replicates pos at the root; XLA folds the add elsewhere.
        pos = (jax.lax.broadcasted_iota(i32, (B, L), 1)
               + jnp.minimum(lens, 0))
        valid = pos < lens

        member: Dict[int, jnp.ndarray] = {}
        for cid in sorted(span_classes | count_classes):
            member[cid] = _membership(rows, intervals[cid],
                                      comp_intervals[cid]) & valid

        # Mosaic-layout discipline (see _membership): every i1 seed must be
        # data-dependent, or the Pallas compile trips an invalid replicated
        # relayout.  true/false columns derive from lens; lit chains start
        # at the first byte compare.
        true_col = lens >= 0              # always true, never replicated
        cur0 = jnp.minimum(lens, 0)       # always 0,   never replicated

        lit_ok: Dict[bytes, jnp.ndarray] = {}
        for lit in sorted(literals):
            data = np.frombuffer(lit, dtype=np.uint8)
            m = None
            for i, ch in enumerate(data):
                shifted = rows if i == 0 else jnp.concatenate(
                    [rows[:, i:], jnp.zeros((B, i), rows.dtype)], axis=1)
                t = shifted == ch
                m = t if m is None else (m & t)
            lit_ok[lit] = m if m is not None else (rows == rows)

        def emit(ops, st: _WalkState, active) -> None:
            """Apply ops to st for rows where `active` (bool [B,1])."""
            for op in ops:
                if isinstance(op, Lit):
                    k = len(op.data)
                    hit = _any_row((pos == st.cur) & lit_ok[op.data])
                    new_ok = (st.ok != 0) & hit & (st.cur + k <= lens)
                    st.ok = jnp.where(active, new_ok.astype(i32), st.ok)
                    st.cur = jnp.where(active,
                                       jnp.minimum(st.cur + k, L32), st.cur)
                elif isinstance(op, Span):
                    m = member[op.class_id]
                    cand = jnp.where(~m & (pos >= st.cur), pos, L32)
                    end = jnp.min(cand, axis=1, keepdims=True)
                    end = jnp.maximum(jnp.minimum(end, lens), st.cur)
                    run = end - st.cur
                    new_ok = (st.ok != 0) & (run >= op.min_len)
                    if op.max_len != INF:
                        new_ok = new_ok & (run <= op.max_len)
                    st.ok = jnp.where(active, new_ok.astype(i32), st.ok)
                    st.cur = jnp.where(active, end, st.cur)
                elif isinstance(op, FixedSpan):
                    new_ok = (st.ok != 0) & (st.cur + op.n <= lens)
                    if op.n > 0:
                        inside = (pos >= st.cur) & (pos < st.cur + op.n)
                        cnt = jnp.sum((member[op.class_id] & inside)
                                      .astype(i32), axis=1, keepdims=True)
                        new_ok = new_ok & (cnt == op.n)
                    st.ok = jnp.where(active, new_ok.astype(i32), st.ok)
                    st.cur = jnp.where(active,
                                       jnp.minimum(st.cur + op.n, L32), st.cur)
                elif isinstance(op, CapStart):
                    st.cap_start[op.cap_id] = jnp.where(
                        active, st.cur, st.cap_start[op.cap_id])
                elif isinstance(op, CapEnd):
                    start = st.cap_start[op.cap_id]
                    st.cap_off[op.cap_id] = jnp.where(
                        active, start, st.cap_off[op.cap_id])
                    st.cap_len[op.cap_id] = jnp.where(
                        active, st.cur - start, st.cap_len[op.cap_id])
                elif isinstance(op, Optional_):
                    before = st.copy()
                    emit(op.body, st, active)
                    take = active & (st.ok != 0)
                    # greedy preference: keep the body where it matched,
                    # revert (skip the group) where it failed
                    merged = _WalkState(st.cur, st.ok, 0, init_caps=False)
                    merged.select(take, st, before)
                    st.cur, st.ok = merged.cur, merged.ok
                    st.cap_off, st.cap_len = merged.cap_off, merged.cap_len
                    st.cap_start = merged.cap_start
                elif isinstance(op, Alt):
                    before = st.copy()
                    chosen_any = cur0         # all-zero i32, data-dependent
                    result = before.copy()
                    remaining = active & (st.ok != 0)
                    for branch in op.branches:
                        trial = before.copy()
                        emit(branch, trial, remaining)
                        chosen = remaining & (trial.ok != 0)
                        merged = _WalkState(result.cur, result.ok, 0,
                                            init_caps=False)
                        merged.select(chosen, trial, result)
                        result = merged
                        chosen_any = chosen_any | chosen.astype(i32)
                        remaining = remaining & ~chosen
                    st.cur = jnp.where(active, result.cur, before.cur)
                    st.ok = jnp.where(active, chosen_any, before.ok)
                    st.cap_off = result.cap_off
                    st.cap_len = result.cap_len
                    st.cap_start = result.cap_start
                else:  # pragma: no cover
                    raise AssertionError(op)

        def emit_reverse(ops, st: _WalkState, active, floor) -> None:
            """Right-to-left walk: st.cur is the EXCLUSIVE end boundary and
            moves toward 0.  Ops arrive pre-reversed (literal bytes too);
            the original CapEnd (seen first) records the group's right edge
            into cap_start, and CapStart closes it."""
            for op in ops:
                if isinstance(op, Lit):
                    k = len(op.data)
                    # match the (already reversed) literal ENDING at cur:
                    # forward bytes start at cur-k
                    fwd = op.data[::-1]
                    start = st.cur - k
                    hit = _any_row((pos == start) & lit_ok[fwd]) & (start >= 0)
                    st.ok = jnp.where(active,
                                      ((st.ok != 0) & hit).astype(i32), st.ok)
                    st.cur = jnp.where(active, jnp.maximum(start, 0), st.cur)
                elif isinstance(op, Span):
                    m = member[op.class_id]
                    # last non-member strictly below cur → run starts after it
                    cand = jnp.where(~m & (pos < st.cur), pos, jnp.int32(-1))
                    start = jnp.max(cand, axis=1, keepdims=True) + 1
                    if op.max_len != INF:
                        # bounded-maximal: a finite repeat takes at most
                        # max_len — the bytes below the clamp belong to
                        # whatever precedes (pivot or earlier suffix ops),
                        # whose own checks cascade a genuine mismatch
                        start = jnp.maximum(start, st.cur - op.max_len)
                    # the suffix may not reach below the pivot's minimal end:
                    # bytes under the floor belong to the prefix + pivot
                    start = jnp.maximum(start, floor)
                    start = jnp.minimum(jnp.maximum(start, 0), st.cur)
                    run = st.cur - start
                    new_ok = (st.ok != 0) & (run >= op.min_len)
                    st.ok = jnp.where(active, new_ok.astype(i32), st.ok)
                    st.cur = jnp.where(active, start, st.cur)
                elif isinstance(op, FixedSpan):
                    start = st.cur - op.n
                    new_ok = (st.ok != 0) & (start >= 0)
                    if op.n > 0:
                        inside = (pos >= start) & (pos < st.cur)
                        cnt = jnp.sum((member[op.class_id] & inside)
                                      .astype(i32), axis=1, keepdims=True)
                        new_ok = new_ok & (cnt == op.n)
                    st.ok = jnp.where(active, new_ok.astype(i32), st.ok)
                    st.cur = jnp.where(active, jnp.maximum(start, 0), st.cur)
                elif isinstance(op, CapEnd):
                    # right edge of the group (encountered first in reverse)
                    st.cap_start[op.cap_id] = jnp.where(
                        active, st.cur, st.cap_start[op.cap_id])
                elif isinstance(op, CapStart):
                    end = st.cap_start[op.cap_id]
                    st.cap_off[op.cap_id] = jnp.where(
                        active, st.cur, st.cap_off[op.cap_id])
                    st.cap_len[op.cap_id] = jnp.where(
                        active, end - st.cur, st.cap_len[op.cap_id])
                elif isinstance(op, Optional_):
                    before = st.copy()
                    emit_reverse(op.body, st, active, floor)
                    take = active & (st.ok != 0)
                    merged = _WalkState(st.cur, st.ok, 0, init_caps=False)
                    merged.select(take, st, before)
                    st.cur, st.ok = merged.cur, merged.ok
                    st.cap_off, st.cap_len = merged.cap_off, merged.cap_len
                    st.cap_start = merged.cap_start
                elif isinstance(op, Alt):
                    before = st.copy()
                    chosen_any = cur0         # all-zero i32, data-dependent
                    result = before.copy()
                    remaining = active & (st.ok != 0)
                    for branch in op.branches:
                        trial = before.copy()
                        emit_reverse(branch, trial, remaining, floor)
                        chosen = remaining & (trial.ok != 0)
                        merged = _WalkState(result.cur, result.ok, 0,
                                            init_caps=False)
                        merged.select(chosen, trial, result)
                        result = merged
                        chosen_any = chosen_any | chosen.astype(i32)
                        remaining = remaining & ~chosen
                    st.cur = jnp.where(active, result.cur, before.cur)
                    st.ok = jnp.where(active, chosen_any, before.ok)
                    st.cap_off = result.cap_off
                    st.cap_len = result.cap_len
                    st.cap_start = result.cap_start
                else:  # pragma: no cover
                    raise AssertionError(op)

        all_rows = true_col
        st = _WalkState(cur0, true_col.astype(i32), ncaps)
        emit(top_ops, st, all_rows)

        if pivot2 is not None:
            # double-pivot: prefix | pivot1 | MID-LITERAL | pivot2 | suffix.
            # Locate the boundary literal inside the gap with a min/max
            # reduce, then verify both pivot regions by masked counts
            # (soundness conditions enforced by _try_double_pivot).
            fwd_starts = {k: st.cap_start[k] for k in split_caps}
            rst = st.copy()
            rst.cur = lens
            floor = (st.cur + pivot.min_len + mid_fixed + pivot2.min_len)
            emit_reverse(suffix_ops, rst, all_rows, floor)
            lo1 = st.cur                  # pivot1 start
            hi2 = rst.cur                 # pivot2 exclusive end
            p_lo = lo1 + pivot.min_len
            p_hi = hi2 - mid_fixed - pivot2.min_len
            feasible = (lit_ok[mid_lit.data] & (pos >= p_lo)
                        & (pos <= p_hi))
            if pivot.lazy:                # both lazy: first occurrence
                cand = jnp.where(feasible, pos, L32)
                p = jnp.min(cand, axis=1, keepdims=True)
                found = p < L32
            else:                         # both greedy: last occurrence
                cand = jnp.where(feasible, pos, jnp.int32(-1))
                p = jnp.max(cand, axis=1, keepdims=True)
                found = p >= 0
            p = jnp.clip(p, 0, L32)
            # middle ops run on the shared forward state at cur = p: the
            # literal advances the cursor, cap markers record edges
            st.cur = jnp.where(found, p, lo1)
            st.ok = st.ok & found.astype(i32)
            emit(mid_ops, st, all_rows)
            lo2 = st.cur                  # pivot2 start (= p + |L|)
            run1 = p - lo1
            inside1 = (pos >= lo1) & (pos < p)
            cnt1 = jnp.sum((member[pivot.class_id] & inside1).astype(i32),
                           axis=1, keepdims=True)
            run2 = hi2 - lo2
            inside2 = (pos >= lo2) & (pos < hi2)
            cnt2 = jnp.sum((member[pivot2.class_id] & inside2).astype(i32),
                           axis=1, keepdims=True)
            ok = ((st.ok != 0) & (rst.ok != 0) & found & (hi2 >= lo2)
                  & (cnt1 == run1) & (run1 >= pivot.min_len)
                  & (cnt2 == run2) & (run2 >= pivot2.min_len))
            final = rst
            # caps closed in prefix already live in rst (copied after the
            # prefix walk); caps closed in the MIDDLE were recorded into st
            # after that copy — pull them over
            for k in mid_end_caps:
                final.cap_off[k] = st.cap_off[k]
                final.cap_len[k] = st.cap_len[k]
            # split caps: open in prefix/middle (forward left edge), close
            # in the suffix (reverse right edge)
            for k in split_caps:
                left = jnp.where(
                    found, st.cap_start[k], fwd_starts[k])
                final.cap_off[k] = left
                final.cap_len[k] = rst.cap_start[k] - left
            off = jnp.concatenate(final.cap_off, axis=1)
            length = jnp.concatenate(final.cap_len, axis=1)
            length = jnp.where(ok, length, -1)
            off = jnp.where(ok, off, 0)
            return ok, off, length

        if pivot is not None:
            # snapshot the forward left edges of split captures BEFORE the
            # reverse walk (its CapEnd reuses cap_start for right edges)
            fwd_starts = {k: st.cap_start[k] for k in split_caps}
            # reverse walk from the line end shares the capture state
            rst = st.copy()
            rst.cur = lens
            emit_reverse(suffix_ops, rst, all_rows, st.cur + pivot.min_len)
            # pivot covers [st.cur, rst.cur): must be all pivot-class bytes
            # within the span's length bounds (masked sum — no gathers)
            lo = st.cur
            hi = rst.cur
            run = hi - lo
            inside = (pos >= lo) & (pos < hi)
            cnt = jnp.sum((member[pivot.class_id] & inside).astype(i32),
                          axis=1, keepdims=True)
            ok = (st.ok != 0) & (rst.ok != 0) & (hi >= lo) & (cnt == run)
            ok = ok & (run >= pivot.min_len)
            if pivot.max_len != INF:
                ok = ok & (run <= pivot.max_len)
            # merge captures: split groups open where the FORWARD walk put
            # their CapStart and close at the reverse walk's right edge
            final = rst
            for k in split_caps:
                final.cap_off[k] = fwd_starts[k]
                final.cap_len[k] = rst.cap_start[k] - fwd_starts[k]
            off = jnp.concatenate(final.cap_off, axis=1)
            length = jnp.concatenate(final.cap_len, axis=1)
            length = jnp.where(ok, length, -1)
            off = jnp.where(ok, off, 0)
            return ok, off, length

        ok = (st.ok != 0) & (st.cur == lens)
        off = jnp.concatenate(st.cap_off, axis=1)
        length = jnp.concatenate(st.cap_len, axis=1)
        length = jnp.where(ok, length, -1)
        off = jnp.where(ok, off, 0)
        return ok, off, length

    return core


def build_extract_fn(program: SegmentProgram):
    """Returns jit-able f(rows u8 [B,L], lengths i32 [B]) ->
    (ok bool [B], cap_off i32 [B,C], cap_len i32 [B,C])."""
    core = build_extract_core(program)

    def extract(rows: jnp.ndarray, lengths: jnp.ndarray):
        ok, off, length = core(rows, lengths.astype(jnp.int32)[:, None])
        return ok[:, 0], off, length

    return extract


def build_match_fn(program: SegmentProgram):
    """Returns jit-able f(rows u8 [B,L], lengths i32 [B]) -> (match i32 [B],):
    the same walk as ``build_extract_fn`` with the capture spans left
    behind — one result word a row is all that leaves the device."""
    core = build_extract_core(program)

    def match(rows: jnp.ndarray, lengths: jnp.ndarray):
        ok, _off, _length = core(rows, lengths.astype(jnp.int32)[:, None])
        return (ok[:, 0].astype(jnp.int32),)

    return match


_donation_cached = None


def donation_supported() -> bool:
    """Buffer donation is real on TPU/GPU — XLA reuses the donated input
    HBM for outputs instead of allocating fresh buffers per dispatch (the
    loongstream ring's device-side half).  On CPU jit ignores donation
    with a per-call warning, so the donating variant is never built
    there."""
    global _donation_cached
    if _donation_cached is None:
        try:
            _donation_cached = jax.default_backend() in ("tpu", "gpu")
        except Exception:  # noqa: BLE001 — no backend ⇒ no donation
            _donation_cached = False
    return _donation_cached


class ExtractKernel:
    """Owns the jitted extraction function for one compiled program.

    jit caches per (B, L) geometry internally; callers should quantise shapes
    (see ops/device_batch.py) to bound the number of compilations.

    No donating variant: no output matches the u8 [B,L] rows or the i32
    [B] lengths in shape, so XLA cannot alias them ("Some donated buffers
    were not usable" on the chip) and a second jit per geometry would buy
    nothing.
    """

    family = "extract"

    def __init__(self, program: SegmentProgram):
        from ..compile_watch import watched_jit
        from ..packed_io import packed_entry, span_columns
        self.program = program
        extract = build_extract_fn(program)
        self._fn = watched_jit(extract, self.family)
        #: the streaming path's entry (ops/packed_io.py): one u8 array in,
        #: one int32 [B, 1 + 2C] array out, ``unpack`` splits it back
        self.packed_call, self.unpack = packed_entry(
            extract, span_columns(program.num_caps), self.family)

    def __call__(self, rows, lengths) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        ok, off, length = self._fn(rows, lengths)
        return ok, off, length

    @property
    def num_caps(self) -> int:
        return self.program.num_caps


class MatchKernel:
    """The full-match gate of one compiled program as a program of its own
    (``jit_loong_line_classify`` on the profiler's ``XLA Modules`` line):
    the multiline start-pattern classify, which sees physical lines where
    the record extract sees merged records, and must be told from it in a
    device trace.  XLA on every backend — the walk is the one the Pallas
    extract falls back to, and with no capture columns to write there is
    nothing for a hand-tiled kernel to save."""

    family = "line_classify"

    def __init__(self, program: SegmentProgram):
        from ..compile_watch import watched_jit
        from ..packed_io import packed_entry
        self.program = program
        match = build_match_fn(program)
        self._fn = watched_jit(match, self.family)
        #: the streaming path's entry (ops/packed_io.py), ``[B, 1]`` out
        self.packed_call, self.unpack = packed_entry(
            match, (("i32", None),), self.family)

    def __call__(self, rows, lengths):
        return self._fn(rows, lengths)
