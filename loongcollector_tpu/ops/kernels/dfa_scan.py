"""Batched DFA execution on device (Tier-2 match kernel).

For regular patterns that don't segment-compile (alternation, overlapping
classes), all events advance a shared DFA in lockstep over byte columns.

TPU mapping: gathers from a [S,K] table are per-element and slow, so the
state is carried ONE-HOT [B, S] in bfloat16 and each step contracts
(state ⊗ byte-class one-hot) with a dense [K·S, S] transition matrix on the
MXU:

    z[b, k·S+s] = cls_onehot[b,k] · state[b,s]       (VPU outer product)
    state'      = z @ T                               (MXU matmul)

Byte classes for all positions are precomputed with interval compares
(no LUT gather).  The scan over positions is a lax.scan compiled once per
(dfa, B, L) geometry.  Used by processor_filter and as the match-gate for
capture-free paths; capture-needing Tier-2 patterns go to CPU (SURVEY.md §7).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..regex.charclass import CharClass
from ..regex.dfa import DFA


def _lockstep_core(automaton):
    """The shared half of every lockstep matcher — works for a
    single-pattern DFA and a fused multi-accept automaton alike (both
    carry num_states/num_classes/transitions/start/byte_class_intervals).

    Returns (K, byte_classes, run): ``byte_classes`` classifies a [B, L]
    byte tensor via interval compares (no LUT gather); ``run(cls)``
    advances all rows in lockstep (``run(cls, lo, hi)`` over the columns
    ``[lo, hi)`` alone, for a caller whose other columns are all frozen)
    — state carried ONE-HOT [B, S] in bfloat16, each step contracting
    (state ⊗ class one-hot) with the dense [(K+1)·S, S] transition tensor
    on the MXU, class K being the identity freeze class — and returns the
    final one-hot states.  The
    builders below differ only in how they VALIDITY-mask the class ids
    (whole row vs span) and what they read off the final states (accept
    bit vs tag bitmask)."""
    S = automaton.num_states
    K = automaton.num_classes
    # dense transition tensor T[k*S+s, s'] = 1 iff δ(s, k) = s'
    T = np.zeros((K * S, S), dtype=np.float32)
    for s in range(S):
        for k in range(K):
            T[k * S + s, int(automaton.transitions[s, k])] = 1.0
    T_dev = jnp.asarray(T, dtype=jnp.bfloat16)
    # extend T with an identity block for the freeze class
    T_ext = jnp.concatenate([T_dev, jnp.eye(S, dtype=jnp.bfloat16)], axis=0)
    class_intervals = automaton.byte_class_intervals()

    def byte_classes(rows: jnp.ndarray) -> jnp.ndarray:
        """uint8 [B, L] -> int32 [B, L] class ids via interval compares."""
        cls = jnp.zeros(rows.shape, dtype=jnp.int32)
        for k in range(1, K):  # class 0 is the default
            m = jnp.zeros(rows.shape, dtype=bool)
            for lo, hi in class_intervals[k]:
                if lo == hi:
                    m = m | (rows == lo)
                else:
                    m = m | ((rows >= lo) & (rows <= hi))
            cls = jnp.where(m, k, cls)
        return cls

    def run(cls: jnp.ndarray, lo=None, hi=None) -> jnp.ndarray:
        B = cls.shape[0]
        state0 = jax.nn.one_hot(automaton.start, S, dtype=jnp.bfloat16)
        state0 = jnp.broadcast_to(state0, (B, S))

        def step(state, cls_t):
            # cls_t: [B] int32
            coh = jax.nn.one_hot(cls_t, K + 1, dtype=jnp.bfloat16)  # [B, K+1]
            z = (coh[:, :, None] * state[:, None, :]).reshape(B, (K + 1) * S)
            nxt = jnp.dot(z, T_ext, preferred_element_type=jnp.bfloat16)
            return nxt, None

        cols = cls.T                                       # [L, B]
        if lo is None:
            final, _ = jax.lax.scan(step, state0, cols)    # scan over L
            return final
        # the columns [lo, hi) alone (traced bounds): every column outside
        # carries the freeze class in every row, so stepping it is a no-op
        return jax.lax.fori_loop(
            lo, hi,
            lambda t, state: step(state, jax.lax.dynamic_index_in_dim(
                cols, t, 0, keepdims=False))[0],
            state0)

    return K, byte_classes, run


def build_dfa_match_fn(dfa: DFA):
    """Returns jit-able f(rows u8 [B,L], lengths i32 [B]) -> ok bool [B]."""
    K, byte_classes, run = _lockstep_core(dfa)
    accepting = jnp.asarray(dfa.accepting)

    def match(rows: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
        L = rows.shape[1]
        cls = byte_classes(rows)                                   # [B, L]
        pos_valid = jnp.arange(L, dtype=jnp.int32)[None, :] < lengths[:, None]
        # past-the-end positions freeze the state: encode as class K (identity)
        cls = jnp.where(pos_valid, cls, K)
        final_state = jnp.argmax(run(cls), axis=1)
        return jnp.take(accepting, final_state)

    return match


def build_dfa_span_match_fn(dfa: DFA):
    """jit-able f(rows u8 [B,L], lengths i32 [B], starts i32 [B],
    spanlens i32 [B]) -> ok bool [B]: full-match of the DFA against the
    row-relative SPAN [starts, starts+spanlens) of each row instead of the
    whole row.

    loongresident: this is the inter-stage composition primitive of the
    fused pipeline program — a filter condition on a field the in-program
    extract stage just captured runs here with the capture spans still
    DEVICE-RESIDENT (no host bounce, no re-pack).  The lockstep advance is
    the single-pattern match kernel's; positions outside the span carry
    the identity freeze class, so the automaton only consumes the field
    bytes.  A row whose span is absent (spanlen < 0, the failed-parse
    convention) never matches — mirroring the staged filter's
    ``ok & src.present`` algebra."""
    K, byte_classes, run = _lockstep_core(dfa)
    accepting = jnp.asarray(dfa.accepting)

    def match(rows: jnp.ndarray, lengths: jnp.ndarray,
              starts: jnp.ndarray, spanlens: jnp.ndarray) -> jnp.ndarray:
        L = rows.shape[1]
        cls = byte_classes(rows)
        pos = jnp.arange(L, dtype=jnp.int32)[None, :]
        span_end = starts + jnp.maximum(spanlens, 0)
        inside = ((pos >= starts[:, None]) & (pos < span_end[:, None])
                  & (pos < lengths[:, None]))
        cls = jnp.where(inside, cls, K)    # freeze outside the span
        final_state = jnp.argmax(run(cls), axis=1)
        return jnp.take(accepting, final_state) & (spanlens >= 0)

    return match


def first_pattern(tags: np.ndarray) -> np.ndarray:
    """Host: accept-tag bitmasks -> int32 index of the lowest set bit (the
    first pattern of the set that matches), -1 for a mask of 0."""
    tags = np.asarray(tags).astype(np.int64)
    low = tags & -tags
    return np.where(low != 0, np.log2(np.maximum(low, 1)), -1).astype(np.int32)


def build_span_label_fn(fdfa):
    """jit-able f(rows u8 [B,L], lengths i32 [B], starts i32 [B],
    spanlens i32 [B]) -> label i32 [B]: ONE walk of the fused multi-accept
    automaton over each row's row-relative SPAN [starts, starts+spanlens),
    read off as the index of the LOWEST pattern of the set that fully
    matches the span (first match wins), -1 where none does and -1 where
    the span is absent (spanlen < 0: the producer did not parse the row).

    The rule list of a classifier in the fused program: the span validity
    mask is ``build_dfa_span_match_fn``'s, the automaton
    ``build_fused_scan_fn``'s, and the walk steps only the columns some
    row's span covers — ``[min start, max end)`` of the batch — because
    every other column is the freeze class in every row.  Its cost is the
    automaton's states times classes a step, whatever the number of
    patterns: the choice among them is a [S] table read off the final
    state."""
    K, byte_classes, run = _lockstep_core(fdfa)
    # per state, 1 + the lowest accepting pattern (0: none): small whole
    # numbers, exact in bfloat16, so the read-off is one contraction with
    # the one-hot final state and no gather
    first = first_pattern(fdfa.accept_tags) + 1
    first_dev = jnp.asarray(first, dtype=jnp.bfloat16)

    def label(rows: jnp.ndarray, lengths: jnp.ndarray,
              starts: jnp.ndarray, spanlens: jnp.ndarray) -> jnp.ndarray:
        L = rows.shape[1]
        cls = byte_classes(rows)
        pos = jnp.arange(L, dtype=jnp.int32)[None, :]
        present = spanlens >= 0
        span_end = jnp.minimum(starts + jnp.maximum(spanlens, 0), lengths)
        inside = (pos >= starts[:, None]) & (pos < span_end[:, None])
        cls = jnp.where(inside, cls, K)    # freeze outside the span
        lo = jnp.clip(jnp.min(jnp.where(present, starts, L)), 0, L)
        hi = jnp.clip(jnp.max(jnp.where(present, span_end, 0)), 0, L)
        final = run(cls, lo, hi)
        got = jnp.dot(final, first_dev, preferred_element_type=jnp.float32)
        return jnp.where(present, got.astype(jnp.int32) - 1, -1)

    return label


class DFASpanMatchKernel:
    """Owns the jitted span-bound match for one DFA — the per-stage
    (demoted) twin of the in-program span condition: the fused dispatcher
    re-runs a faulted chunk through this kernel with the producer stage's
    materialised spans, so demotion costs dispatches, never answers."""

    def __init__(self, dfa: DFA):
        from ..compile_watch import watched_jit
        self.dfa = dfa
        self._fn = watched_jit(build_dfa_span_match_fn(dfa),
                               "dfa_span_match")

    def __call__(self, rows, lengths, starts, spanlens) -> np.ndarray:
        return self._fn(rows, lengths, starts, spanlens)


class LazySpanMatchKernel:
    """DFASpanMatchKernel built on FIRST call.  The fused planner stores
    this as a capture-bound keep-condition's staged twin, so pipeline
    init never pays the transition-matrix build and host→device constant
    transfer for a kernel only the (rare) demotion path runs."""

    __slots__ = ("dfa", "_k")

    def __init__(self, dfa: DFA):
        self.dfa = dfa
        self._k = None

    def __call__(self, rows, lengths, starts, spanlens) -> np.ndarray:
        if self._k is None:
            self._k = DFASpanMatchKernel(self.dfa)
        return self._k(rows, lengths, starts, spanlens)


def build_fused_scan_fn(fdfa):
    """jit-able f(rows u8 [B,L], lengths i32 [B]) -> tags u32-as-i32 [B].

    loongfuse: the lockstep advance is IDENTICAL to the single-pattern
    match kernel (state one-hot ⊗ class one-hot contracted with the dense
    transition tensor on the MXU) — the widening is in the EPILOGUE, a
    multi-accept one-hot contraction: final [B,S] @ tag-bit matrix [S,P]
    yields per-pattern indicators, folded into one accept-tag bitmask.
    One device pass classifies every pattern of the fused set at once."""
    S = fdfa.num_states
    K, byte_classes, run = _lockstep_core(fdfa)
    P = max(int(fdfa.accept_tags.max()).bit_length(), 1)
    tag_bits = np.zeros((S, P), dtype=np.float32)
    for s in range(S):
        for p in range(P):
            if int(fdfa.accept_tags[s]) & (1 << p):
                tag_bits[s, p] = 1.0
    bits_dev = jnp.asarray(tag_bits, dtype=jnp.bfloat16)
    # bit 31 (MAX_PATTERNS=32) does not fit a python-int->int32 cast;
    # build u32 and bit-cast — callers read the result as uint32 anyway
    pow2 = jnp.asarray(
        np.array([1 << p for p in range(P)], dtype=np.uint32).view(np.int32))

    def scan_tags(rows: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
        L = rows.shape[1]
        cls = byte_classes(rows)
        pos_valid = jnp.arange(L, dtype=jnp.int32)[None, :] < lengths[:, None]
        cls = jnp.where(pos_valid, cls, K)      # freeze class past the end
        final = run(cls)
        # multi-accept one-hot contraction: per-pattern indicator columns,
        # folded to a bitmask on the VPU
        ind = jnp.dot(final, bits_dev,
                      preferred_element_type=jnp.float32)
        ind_i = (ind > 0.5).astype(jnp.int32)
        return jnp.sum(ind_i * pow2[None, :], axis=1)

    return scan_tags


class FusedScanKernel:
    """Device execution of a fused multi-accept automaton.  One invocation
    returns the accept-tag bitmask for every event in the batch —
    `invocations` counts dispatches so tests can assert that a ≥4-pattern
    set classifies in a SINGLE kernel pass."""

    def __init__(self, fdfa):
        from ..compile_watch import watched_jit
        self.fdfa = fdfa
        self._fn = watched_jit(build_fused_scan_fn(fdfa), "fused_scan")
        self._fn_donated = None
        self.invocations = 0

    def __call__(self, rows, lengths) -> np.ndarray:
        self.invocations += 1
        return self._fn(rows, lengths)

    def donated_call(self, rows, lengths) -> np.ndarray:
        """Streaming-path variant (see DFAMatchKernel.donated_call)."""
        from .field_extract import donation_supported
        if not donation_supported():
            return self.__call__(rows, lengths)
        if self._fn_donated is None:
            from ..compile_watch import watched_jit
            self._fn_donated = watched_jit(build_fused_scan_fn(self.fdfa),
                                           "fused_scan",
                                           donate_argnums=(0, 1))
        self.invocations += 1
        return self._fn_donated(rows, lengths)


class DFAMatchKernel:
    def __init__(self, dfa: DFA):
        from ..compile_watch import watched_jit
        self.dfa = dfa
        self._fn = watched_jit(build_dfa_match_fn(dfa), "dfa_match")
        self._fn_donated = None

    def __call__(self, rows, lengths) -> np.ndarray:
        return self._fn(rows, lengths)

    def donated_call(self, rows, lengths) -> np.ndarray:
        """Streaming-path variant: donate the per-dispatch staging buffers
        so XLA reuses their HBM.  NOT safe for callers that re-use a
        device-resident input across calls; gated off on CPU, where jit
        ignores donation with a per-call warning."""
        from .field_extract import donation_supported
        if not donation_supported():
            return self._fn(rows, lengths)
        if self._fn_donated is None:
            from ..compile_watch import watched_jit
            self._fn_donated = watched_jit(build_dfa_match_fn(self.dfa),
                                           "dfa_match",
                                           donate_argnums=(0, 1))
        return self._fn_donated(rows, lengths)


#: a ``mask`` stage's count for a row it does not decide: the span is absent,
#: it holds a byte >= 0x80, or (the demoted path's) the host decides it;
#: ``slots + 1`` is a row with more matches than the stage hands back
MASK_ABSENT = -1
MASK_NON_ASCII = -2
MASK_DEMOTED = -3


def _in_class(rows: jnp.ndarray, mask: np.ndarray) -> jnp.ndarray:
    """Byte-class membership by interval compares (no LUT gather): the
    class's own intervals or its complement's, whichever is fewer."""
    mask = np.asarray(mask, dtype=bool)
    negate = len(CharClass(~mask).intervals()) < len(
        CharClass(mask).intervals())
    hit = jnp.zeros(rows.shape, dtype=bool)
    for lo, hi in CharClass(~mask if negate else mask).intervals():
        hit = hit | ((rows == lo) if lo == hi
                     else (rows >= lo) & (rows <= hi))
    return ~hit if negate else hit


def build_span_mask_fn(plan):
    """jit-able f(rows u8 [B,L], lengths i32 [B], starts i32 [B],
    spanlens i32 [B]) -> (count i32 [B], content_start i32 [B, S],
    content_end i32 [B, S]): the find-all walk of a masking pair (a
    `MaskPlan`, ``ops/regex/fuse.py``) over each row's row-relative SPAN,
    leftmost-first and non-overlapping, as ``re.sub`` takes matches.

    Every column is decided at once, with no sequential walk over the
    columns: for each of the prefix's fixed-length alternatives, shifted
    class compares say where it matches wholly inside the span; a reverse
    running minimum of "the next byte outside the content class, or the
    span's end" gives each content's greedy extent, so a match never
    reaches past the field; at one start the lowest alternative whose
    content count reaches ``lo`` wins.  Then ``S`` sequential picks, each
    the leftmost start at or after the cursor, the cursor moving to the
    match's end.  ``count`` is the matches found (their content spans in
    the first ``count`` slots), ``S + 1`` where a row has more than the
    stage hands back (never where only the first match is replaced),
    `MASK_ABSENT` for an absent span and `MASK_NON_ASCII` for a span with a
    byte >= 0x80: those rows are the host's."""
    alts = [[np.asarray(m, dtype=bool) for m in alt]
            for alt in plan.alternatives]
    wide = max(len(a) for a in alts)
    S = plan.slots
    lo_count, hi_count = plan.lo, plan.hi

    def mask(rows: jnp.ndarray, lengths: jnp.ndarray, starts: jnp.ndarray,
             spanlens: jnp.ndarray):
        B, L = rows.shape
        pos = jnp.arange(L, dtype=jnp.int32)[None, :]
        present = spanlens >= 0
        end = jnp.minimum(starts + jnp.maximum(spanlens, 0), lengths)
        padded = jnp.pad(rows, ((0, 0), (0, wide + 1)))
        P = L + wide + 1
        pos_p = jnp.arange(P, dtype=jnp.int32)[None, :]
        stop = (pos_p >= end[:, None]) | ~_in_class(padded, plan.content)
        # the first position at or after each column where the content stops
        nxt = jax.lax.cummin(jnp.where(stop, pos_p, P), axis=1, reverse=True)
        valid = jnp.zeros((B, L), dtype=bool)
        c_start = jnp.zeros((B, L), dtype=jnp.int32)
        c_end = jnp.zeros((B, L), dtype=jnp.int32)
        for alt in reversed(alts):           # the lowest alternative wins
            k = len(alt)
            hit = (pos >= starts[:, None]) & (pos + k <= end[:, None])
            for j, cls in enumerate(alt):
                hit = hit & _in_class(padded[:, j:j + L], cls)
            run = nxt[:, k:k + L] - (pos + k)
            if hi_count is not None:
                run = jnp.minimum(run, hi_count)
            ok = hit & (run >= lo_count)
            valid = valid | ok
            c_start = jnp.where(ok, pos + k, c_start)
            c_end = jnp.where(ok, pos + k + run, c_end)
        cursor = starts
        count = jnp.zeros(B, dtype=jnp.int32)
        firsts, lasts = [], []
        for _ in range(S):
            at = jnp.min(jnp.where(valid & (pos >= cursor[:, None]), pos, L),
                         axis=1)
            found = at < L
            here = pos == at[:, None]          # no column where none found
            cs = jnp.sum(jnp.where(here, c_start, 0), axis=1)
            ce = jnp.sum(jnp.where(here, c_end, 0), axis=1)
            firsts.append(cs)
            lasts.append(ce)
            count = count + found.astype(jnp.int32)
            cursor = jnp.where(found, ce, cursor)
        if plan.replace_all:
            more = jnp.any(valid & (pos >= cursor[:, None]), axis=1)
            count = jnp.where(more, S + 1, count)
        inside = (pos >= starts[:, None]) & (pos < end[:, None])
        wide_byte = jnp.any(inside & (rows >= 0x80), axis=1)
        count = jnp.where(wide_byte, MASK_NON_ASCII, count)
        count = jnp.where(present, count, MASK_ABSENT)
        return count, jnp.stack(firsts, axis=1), jnp.stack(lasts, axis=1)

    return mask
