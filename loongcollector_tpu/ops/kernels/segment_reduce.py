"""Segment-reduce twins for the loongagg metric fold.

The native `lct_group_reduce` is the production substrate: hash the
(window slot, key spans) identity per row, then fold the value column
per group — sum/count/min/max/last plus the metrics.py-shaped log2-bucket
histogram — in f64, in row order.  This module carries its two siblings:

* the **numpy twin** — the no-native tier and the shared reference.  The
  segment identity comes from one vectorised length-prefixed key-matrix
  gather + ``np.unique`` remapped to first-seen order (the native group-id
  order), and the fold accumulates with ``np.add.at`` — sequential adds in
  row index order, the exact accumulation order of the native loop, so
  sums are **bit-identical**, not merely close (min/max/count/hist are
  order-free).  Value-span parsing is the one per-row loop in this tier
  (no vectorised strtod exists); it is the degraded path by contract —
  the native plane is the throughput claim;

* the **device twin** (`SegmentReduceKernel`) — the wide data-parallel
  half for the accelerator, `jax.ops.segment_*` over a padded batch slot:
  ONE jitted dispatch per ``device_batch`` geometry computes every
  aggregate including the histogram (a segment-sum over ``seg * NB +
  bucket``).  Keying, value parsing and bucket ids stay on the host (f64,
  shared helpers — frexp on f32 would disagree at power-of-two
  boundaries); the device owns the reduction, ParPaRaw-style.  Sums
  accumulate in f32 on default-precision backends, so the
  ``scripts/agg_equivalence.py`` gate compares device sums with a stated
  tolerance and everything else exactly.

All three substrates are differentially gated (lint.sh + tier-1) — same
partition, same aggregates, or the gate fails per row.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Optional

import numpy as np

#: metrics.py Histogram geometry applied to metric VALUES: base 1.0
#: (values ≤ 1 land in bucket 0), 40 log2 buckets + the +Inf slot
HIST_BASE = 1.0
N_HIST = 41

#: the strtod-subset value grammar shared with the native plane (see
#: lct_group_reduce): sign, decimal digits with optional fraction and
#: exponent, or inf/infinity.  NaN is invalid BY GRAMMAR — it would make
#: min/max accumulation order-visible across substrates.
_VALUE_RE = re.compile(
    rb"^[+-]?(?:(?:\d+\.?\d*|\.\d+)(?:[eE][+-]?\d+)?|"
    rb"[iI][nN][fF](?:[iI][nN][iI][tT][yY])?)$")


def hist_bucket(values: np.ndarray, base: float = HIST_BASE,
                n_hist: int = N_HIST) -> np.ndarray:
    """Vectorised metrics.py bucket shape on f64: v <= base (and
    negatives) -> 0, +inf -> the last slot, else ceil(log2(v/base))
    clamped.  Shared by the numpy twin and the device path (bucket ids
    are computed on the host in f64 for all substrates)."""
    v = np.asarray(values, dtype=np.float64)
    m, e = np.frexp(np.where(v > base, v / base, 1.0))
    idx = np.where(m == 0.5, e - 1, e).astype(np.int64)
    idx = np.clip(idx, 0, n_hist - 1)
    idx = np.where(v > base, idx, 0)
    return np.where(np.isinf(v) & (v > 0), n_hist - 1, idx)


#: vector parse only reads this many bytes per span; longer tokens (rare:
#: huge paddings, absurd precision) take the per-row reference path
_VEC_WIDTH = 32
#: ≤ 15 decimal digits ⇒ the mantissa integer is exact in f64 and
#: m / 10^frac is a single correctly-rounded division (Clinger) — the
#: same fast-path argument the native strtod subset uses
_VEC_MAX_DIGITS = 15


def _parse_values_rows(arena: np.ndarray, val_offs: np.ndarray,
                       val_lens: np.ndarray, rows, values: np.ndarray,
                       valid: np.ndarray) -> None:
    """Reference per-row parse of selected rows: the shared grammar regex
    gates, Python float() converts (correctly rounded ⇒ bit-identical to
    the native strtod)."""
    buf = memoryview(np.ascontiguousarray(arena))
    for i in rows:
        ln = int(val_lens[i])
        if ln < 0:
            continue
        off = int(val_offs[i])
        tok = bytes(buf[off:off + ln]).strip(b" \t")
        if not _VALUE_RE.match(tok):
            continue
        values[i] = float(tok)
        valid[i] = True


def parse_values(arena: np.ndarray, val_offs: np.ndarray,
                 val_lens: np.ndarray):
    """(values f64 [n], valid bool [n]) from value text spans.

    The common shape — optional sign, ≤ 15 digits, at most one '.' , no
    exponent — parses VECTORISED: one byte-matrix gather, per-column
    digit folds into an exact int64 mantissa, one correctly-rounded
    division by an exact power of ten.  Clinger's fast-path argument
    makes that bit-identical to Python float(), which the
    scripts/agg_equivalence.py gate asserts against the reference loop.
    Everything else (exponents, inf, over-long, malformed) drops to the
    per-row reference path — the counted exception, not the steady
    state.  The per-row float() loop used to price every twin's fold, not
    the kernel."""
    n = len(val_offs)
    values = np.zeros(n, dtype=np.float64)
    valid = np.zeros(n, dtype=bool)
    if n == 0:
        return values, valid
    offs = np.asarray(val_offs, dtype=np.int64)
    lens = np.asarray(val_lens, dtype=np.int64)
    W = min(int(lens.max()), _VEC_WIDTH)
    if W <= 0:
        # nothing with a positive length; empty spans are invalid by
        # grammar, negative lengths are the absent convention
        return values, valid
    arena_hi = max(len(arena) - 1, 0)
    idx = offs[:, None] + np.arange(W, dtype=np.int64)[None, :]
    np.clip(idx, 0, arena_hi, out=idx)
    mat = arena[idx] if len(arena) else np.zeros((n, W), np.uint8)
    inrow = np.arange(W, dtype=np.int64)[None, :] < lens[:, None]
    SPACE = np.uint8(0x20)
    mat = np.where(inrow, mat, SPACE)      # pad reads as trimmable space
    is_sp = (mat == 0x20) | (mat == 0x09)
    nonsp = ~is_sp
    any_ns = nonsp.any(axis=1)
    first = np.argmax(nonsp, axis=1)
    last = W - 1 - np.argmax(nonsp[:, ::-1], axis=1)
    colpos = np.arange(W, dtype=np.int64)[None, :]
    is_digit = (mat >= 0x30) & (mat <= 0x39)
    is_dot = mat == 0x2E
    sign_byte = mat[np.arange(n), first]
    has_sign = (sign_byte == 0x2B) | (sign_byte == 0x2D)
    body_lo = first + has_sign
    within = (colpos >= body_lo[:, None]) & (colpos <= last[:, None])
    digits = np.count_nonzero(is_digit & within, axis=1)
    dots = np.count_nonzero(is_dot & within, axis=1)
    clean = (within & ~(is_digit | is_dot)).sum(axis=1) == 0
    fast = (any_ns & clean & (dots <= 1) & (digits >= 1)
            & (digits <= _VEC_MAX_DIGITS) & (lens <= _VEC_WIDTH)
            & (body_lo <= last))
    # per-column mantissa fold: m = m*10 + d over the token's digit
    # positions (int64-exact: ≤ 15 digits), frac counts digits after the
    # dot — vector ops per COLUMN, never per row
    m = np.zeros(n, dtype=np.int64)
    frac = np.zeros(n, dtype=np.int64)
    seen_dot = np.zeros(n, dtype=bool)
    for c in range(W):
        active = fast & within[:, c]
        d = is_digit[:, c] & active
        m = np.where(d, m * 10 + (mat[:, c].astype(np.int64) - 0x30), m)
        frac = np.where(d & seen_dot, frac + 1, frac)
        seen_dot = seen_dot | (is_dot[:, c] & active)
    v = m.astype(np.float64) / np.power(10.0, frac)
    v = np.where(sign_byte == 0x2D, -v, v)
    values[fast] = v[fast]
    valid[fast] = True
    # rows longer than the window may hide their token past byte W (all
    # leading spaces): they must take the reference path, not "invalid"
    slow = np.nonzero((lens >= 0) & ~fast & (any_ns | (lens > W)))[0]
    if len(slow):
        _parse_values_rows(arena, val_offs, val_lens, slow, values, valid)
    return values, valid


def _key_matrix(arena: np.ndarray, slots: np.ndarray,
                key_offs: np.ndarray, key_lens: np.ndarray):
    """Length-prefixed key bytes as one uint8 matrix [n, W] — the
    vectorised identity the first-seen grouping runs np.unique over.
    The i32 length prefix keeps absent (-1) distinct from empty and
    ("ab","") distinct from ("a","b"); the slot rides as an i64 prefix
    column so window identity is part of the segment key, exactly as in
    the native hash.

    Returns (mat, widths): ``widths`` is the per-key padded column width
    (the batch max per key) — matrix rows are only comparable ACROSS
    batches together with their widths, because the zero padding between
    key segments is width-dependent (the merge-side intern cache keys on
    both)."""
    n, K = key_lens.shape
    parts = [np.ascontiguousarray(slots, dtype="<i8").view(
        np.uint8).reshape(n, 8)]
    arena_hi = max(len(arena) - 1, 0)
    widths = []
    for k in range(K):
        lens = key_lens[:, k]
        parts.append(np.ascontiguousarray(lens, dtype="<i4").view(
            np.uint8).reshape(n, 4))
        m = int(lens.max()) if n else 0
        widths.append(max(m, 0))
        if m > 0:
            idx = key_offs[:, k, None] + np.arange(m, dtype=np.int64)[None, :]
            np.clip(idx, 0, arena_hi, out=idx)
            body = (arena[idx] if len(arena)
                    else np.zeros((n, m), np.uint8))
            mask = np.arange(m, dtype=np.int32)[None, :] < lens[:, None]
            parts.append(np.where(mask, body, 0).astype(np.uint8))
    return np.concatenate(parts, axis=1), tuple(widths)


def _first_seen_ids_exact(mat: np.ndarray):
    """Reference grouping: np.unique over the whole byte matrix is
    lexicographic, so remap through the argsort of first occurrences to
    match the native assignment order.  Sorting the full matrix is slow;
    `_first_seen_ids` keeps it for the empty matrix and a hash collision."""
    _uniq, first_idx, inv = np.unique(mat, axis=0, return_index=True,
                                      return_inverse=True)
    order = np.argsort(first_idx, kind="stable")
    remap = np.empty(len(order), dtype=np.int64)
    remap[order] = np.arange(len(order))
    return remap[np.asarray(inv).reshape(-1)], first_idx[order]


def _first_seen_ids(mat: np.ndarray):
    """(group ids [rows] in first-seen order, representative row per
    group).

    Fast path: a vectorised 64-bit FNV-1a over the matrix columns gives
    one hash per row; np.unique on the [n] u64 vector replaces the
    lexicographic sort of the full byte matrix.  Grouping stays EXACT —
    every row's bytes are compared against its hash-group
    representative's (one gather + one matrix compare); any mismatch (a
    64-bit collision, astronomically rare) falls back to the byte-exact
    reference, so the partition and the first-seen id order are always
    identical to the native assignment."""
    n, W = mat.shape
    if n == 0:
        return _first_seen_ids_exact(mat)
    h = np.full(n, 0xcbf29ce484222325, dtype=np.uint64)
    prime = np.uint64(0x100000001B3)
    for c in range(W):
        h = (h ^ mat[:, c].astype(np.uint64)) * prime
    _uniq, first_idx, inv = np.unique(h, return_index=True,
                                      return_inverse=True)
    inv = np.asarray(inv).reshape(-1)
    rep_rows = first_idx[inv]
    if not np.array_equal(mat, mat[rep_rows]):
        return _first_seen_ids_exact(mat)
    order = np.argsort(first_idx, kind="stable")
    remap = np.empty(len(order), dtype=np.int64)
    remap[order] = np.arange(len(order))
    return remap[inv], first_idx[order]


@dataclass
class BatchFold:
    """One batch's partial fold, identical shape across substrates."""

    group_id: np.ndarray   # i32/i64 [n]; -1 = invalid-value row
    rep_row: np.ndarray    # [G] first row index per group
    sum: np.ndarray        # f64 [G]
    count: np.ndarray      # i64 [G]
    min: np.ndarray        # f64 [G]
    max: np.ndarray        # f64 [G]
    last: np.ndarray       # f64 [G]
    hist: np.ndarray       # i64 [G, N_HIST]
    #: [G, W] uint8 key-matrix rows of the representatives, when the
    #: substrate already gathered them (numpy/device twins): the fold's
    #: hash-key bytes, reusable by the window merge as interning keys so
    #: steady-state batches never rebuild per-group key tuples.  None on
    #: the native substrate.
    rep_key_blob: Optional[np.ndarray] = None
    #: per-key padded widths of ``rep_key_blob`` (see _key_matrix): blob
    #: rows are only comparable across batches together with these —
    #: interning on the bytes alone would let two different key tuples
    #: from different-width batches collide
    key_widths: Optional[tuple] = None

    @property
    def n_groups(self) -> int:
        return int(len(self.rep_row))

    @property
    def n_invalid(self) -> int:
        return int(np.count_nonzero(self.group_id < 0))


def fold_batch_numpy(arena: np.ndarray, slots: np.ndarray,
                     key_offs: np.ndarray, key_lens: np.ndarray,
                     val_offs: np.ndarray, val_lens: np.ndarray,
                     hist_base: float = HIST_BASE,
                     n_hist: int = N_HIST) -> BatchFold:
    """The numpy substrate / shared reference (see module docstring)."""
    n = len(slots)
    values, valid = parse_values(arena, val_offs, val_lens)
    group_id = np.full(n, -1, dtype=np.int32)
    vrows = np.nonzero(valid)[0]
    if len(vrows) == 0:
        z = np.zeros(0)
        return BatchFold(group_id, np.zeros(0, np.int32), z,
                         np.zeros(0, np.int64), z, z, z,
                         np.zeros((0, n_hist), np.int64))
    mat, widths = _key_matrix(arena, slots[vrows], key_offs[vrows],
                              key_lens[vrows])
    ids, first = _first_seen_ids(mat)
    group_id[vrows] = ids
    rep_row = vrows[first].astype(np.int32)
    G = int(ids.max()) + 1
    vv = values[vrows]
    sums = np.zeros(G, dtype=np.float64)
    # np.add.at applies adds in index order — the native loop's exact
    # accumulation order, which is what makes sums bit-identical (np.sum
    # style pairwise reduction would not be).  inf + -inf inside one key
    # is legal (sum -> NaN on every substrate): silence the warning
    with np.errstate(invalid="ignore"):
        np.add.at(sums, ids, vv)
    counts = np.bincount(ids, minlength=G).astype(np.int64)
    order = np.argsort(ids, kind="stable")
    sv = vv[order]
    starts = np.searchsorted(ids[order], np.arange(G))
    mins = np.minimum.reduceat(sv, starts)
    maxs = np.maximum.reduceat(sv, starts)
    ends = np.append(starts[1:], len(sv))
    last = sv[ends - 1]
    hist = np.zeros((G, n_hist), dtype=np.int64)
    np.add.at(hist, (ids, hist_bucket(vv, hist_base, n_hist)), 1)
    return BatchFold(group_id, rep_row, sums, counts, mins, maxs, last,
                     hist, rep_key_blob=mat[first], key_widths=widths)


def fold_batch_native(arena: np.ndarray, slots: np.ndarray,
                      key_offs: np.ndarray, key_lens: np.ndarray,
                      val_offs: np.ndarray, val_lens: np.ndarray,
                      hist_base: float = HIST_BASE,
                      n_hist: int = N_HIST) -> Optional[BatchFold]:
    """The native substrate; None when the library is unavailable."""
    from ...native import group_reduce
    res = group_reduce(arena, slots, key_offs, key_lens, val_offs,
                       val_lens, hist_base=hist_base, n_hist=n_hist)
    if res is None:
        return None
    return BatchFold(*res)


# ---------------------------------------------------------------------------
# device twin


def build_reduce_fn(n_hist: int):
    """Returns jit-able f(values f32 [B], seg i32 [B], buckets i32 [B],
    valid bool [B], G static) -> (sum, count, min, max, last, hist).
    Invalid/padding rows route to segment id G — out of range, dropped by
    the scatter, never a branch."""
    import jax
    import jax.numpy as jnp

    def reduce_fn(values, seg, buckets, valid, G):
        seg = jnp.where(valid, seg, G)
        data = jnp.where(valid, values, jnp.float32(0))
        sums = jax.ops.segment_sum(data, seg, num_segments=G)
        cnt = jax.ops.segment_sum(valid.astype(jnp.int32), seg,
                                  num_segments=G)
        mins = jax.ops.segment_min(
            jnp.where(valid, values, jnp.float32(jnp.inf)), seg,
            num_segments=G)
        maxs = jax.ops.segment_max(
            jnp.where(valid, values, jnp.float32(-jnp.inf)), seg,
            num_segments=G)
        idx = jnp.arange(values.shape[0], dtype=jnp.int32)
        last_idx = jax.ops.segment_max(
            jnp.where(valid, idx, jnp.int32(-1)), seg, num_segments=G)
        last = jnp.where(last_idx >= 0,
                         values[jnp.clip(last_idx, 0, None)],
                         jnp.float32(0))
        hist = jax.ops.segment_sum(
            valid.astype(jnp.int32), seg * n_hist + buckets,
            num_segments=G * n_hist).reshape(G, n_hist)
        return sums, cnt, mins, maxs, last, hist

    return reduce_fn


class SegmentReduceKernel:
    """Owns the jitted segment-reduce for one histogram geometry.

    jit caches per (B, G) — `fold_batch` quantises B through
    ``ops.device_batch.pad_batch`` and G to a power of two, so a batch
    slot is ONE dispatch (`dispatch_count` asserted in the device test).
    `donated_call` mirrors the loongstream donated-buffer contract for
    the transient staging arrays."""

    def __init__(self, n_hist: int = N_HIST):
        from ..compile_watch import watched_jit
        self.n_hist = n_hist
        self._fn = watched_jit(build_reduce_fn(n_hist), "segment_reduce",
                               static_argnums=(4,))
        self._fn_donated = None
        self.dispatch_count = 0
        # per-geometry staging buffers (the batch-slot idiom): the padded
        # value/segment/bucket arrays are reused across folds instead of
        # re-allocated per batch (host prep must not price the kernel).
        # Buffers are LEASED out of the pool under the lock and returned
        # after the fold, so two pipelines sharing the module-global
        # kernel never race one tuple yet still overlap their device
        # round trips.
        import threading
        self._staging: dict = {}
        self._staging_lock = threading.Lock()

    def __call__(self, values, seg, buckets, valid, G: int):
        self.dispatch_count += 1
        return self._fn(values, seg, buckets, valid, G)

    def donated_call(self, values, seg, buckets, valid, G: int):
        from .field_extract import donation_supported
        if not donation_supported():
            return self(values, seg, buckets, valid, G)
        if self._fn_donated is None:
            from ..compile_watch import watched_jit
            self._fn_donated = watched_jit(build_reduce_fn(self.n_hist),
                                           "segment_reduce",
                                           static_argnums=(4,),
                                           donate_argnums=(0, 1, 2, 3))
        self.dispatch_count += 1
        return self._fn_donated(values, seg, buckets, valid, G)

    def fold_batch(self, arena: np.ndarray, slots: np.ndarray,
                   key_offs: np.ndarray, key_lens: np.ndarray,
                   val_offs: np.ndarray, val_lens: np.ndarray,
                   hist_base: float = HIST_BASE) -> BatchFold:
        """Device substrate: host keying + bucketing (exact f64), padded
        single-dispatch segment reduction on the accelerator."""
        import jax

        from ..device_batch import pad_batch
        n_hist = self.n_hist
        n = len(slots)
        values, valid = parse_values(arena, val_offs, val_lens)
        group_id = np.full(n, -1, dtype=np.int32)
        vrows = np.nonzero(valid)[0]
        if len(vrows) == 0:
            z = np.zeros(0)
            return BatchFold(group_id, np.zeros(0, np.int32), z,
                             np.zeros(0, np.int64), z, z, z,
                             np.zeros((0, n_hist), np.int64))
        mat, widths = _key_matrix(arena, slots[vrows], key_offs[vrows],
                                  key_lens[vrows])
        ids, first = _first_seen_ids(mat)
        group_id[vrows] = ids
        rep_row = vrows[first].astype(np.int32)
        G = int(ids.max()) + 1
        B = pad_batch(n)
        Gq = 16
        while Gq < G:
            Gq *= 2
        # lease the geometry's staging tuple OUT of the pool (lock held
        # only for the checkout/return, never across the device round
        # trip — concurrent pipelines overlap their folds); a concurrent
        # lease of the same geometry just allocates a transient tuple
        # and the later return drops it
        from ..device_plane import mem_note_alloc, mem_note_free
        with self._staging_lock:
            bufs = self._staging.pop(B, None)
        if bufs is None:
            bufs = (np.zeros(B, dtype=np.float32),
                    np.zeros(B, dtype=np.int32),
                    np.zeros(B, dtype=np.int32),
                    np.zeros(B, dtype=bool))
            # side_arenas ledger (loongxprof): a freshly allocated staging
            # tuple joins the pool's live footprint; a transient tuple
            # dropped at return (pool already holds this geometry) credits
            # back below
            mem_note_alloc("side_arenas", sum(a.nbytes for a in bufs))
        try:
            vals, seg, buckets, ok = bufs
            vals[:n] = values.astype(np.float32)
            vals[n:] = 0
            seg[:n] = group_id.clip(min=0)
            seg[n:] = Gq
            ok[:n] = valid
            ok[n:] = False
            buckets[:n] = hist_bucket(values, hist_base, n_hist)
            buckets[n:] = 0
            out = self.donated_call(vals, seg, buckets, ok, Gq)
            sums, cnt, mins, maxs, last, hist = (np.asarray(a) for a in
                                                 jax.device_get(out))
        finally:
            with self._staging_lock:
                kept = self._staging.setdefault(B, bufs) is bufs
            if not kept:
                mem_note_free("side_arenas", sum(a.nbytes for a in bufs))
        return BatchFold(group_id, rep_row,
                         sums[:G].astype(np.float64),
                         cnt[:G].astype(np.int64),
                         mins[:G].astype(np.float64),
                         maxs[:G].astype(np.float64),
                         last[:G].astype(np.float64),
                         hist[:G].astype(np.int64),
                         rep_key_blob=mat[first], key_widths=widths)


_device_kernel: Optional[SegmentReduceKernel] = None


def device_kernel() -> SegmentReduceKernel:
    global _device_kernel
    if _device_kernel is None:
        _device_kernel = SegmentReduceKernel()
    return _device_kernel


def hist_bucket_scalar(v: float, base: float = HIST_BASE,
                       n_hist: int = N_HIST) -> int:
    """Scalar shape twin for the per-event dict path (exactly the
    vectorised hist_bucket, which itself mirrors metrics.py)."""
    if math.isinf(v) and v > 0:
        return n_hist - 1
    if not v > base:
        return 0
    m, e = math.frexp(v / base)
    idx = e - 1 if m == 0.5 else e
    return min(max(idx, 0), n_hist - 1)
