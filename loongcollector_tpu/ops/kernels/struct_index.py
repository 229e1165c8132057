"""Structural-index device twin (loongstruct stage 1 on the accelerator).

One dispatch indexes a whole batch-ring slot: classify every byte of a
[B, L] row tensor into structural bitmaps — in-string, structural chars,
escaped positions, unescaped quotes — exactly mirroring the native
`lct_struct_index` word masks (differentially asserted in
tests/test_struct_index.py and scripts/struct_equivalence.py).

Formulation notes (the codesign lesson from the in-memory-matching paper:
pick the layout the substrate likes):

* the native plane resolves escapes with simdjson's odd-length
  backslash-run carry trick, word by word.  Here the whole row is one
  tensor, so the same semantics — a position is "escaped" iff it is NOT a
  backslash and the backslash run immediately before it has odd length —
  falls out of an associative max-scan (`last non-backslash position`)
  plus elementwise parity, with no sequential carry at all;
* the in-string mask is the inclusive prefix-XOR of unescaped quotes
  (opening quote inside, closing quote outside), i.e. a cumulative-sum
  parity along the length axis;
* masks pack to 16-bit words (int32-safe on every backend; the native
  uint64 words view as four such words on little-endian hosts).

The kernel is a single jitted function per (mode, B, L) geometry —
`StructIndexKernel.index_batch` packs a columnar group through the same
`ops.device_batch` length buckets the streaming plane uses and counts one
dispatch per slot (asserted single-invocation in the device test).  The
numpy twin below is the no-JAX fallback tier and the reference for both.

Who runs it: no served path calls `StructIndexKernel` — tests, the
equivalence gate and the compile check do.  Its four masks are as large as
the rows they index, and nothing turned device-made masks into JSON field
spans, so `processor_parse_json_tpu` parsed on the host's native plane in
every pipeline until PR 27.  What the served path runs of this module is
`_index_core`: the `json_fields` stage (json_fields.py) starts from its
masks and goes on, on the device, to the value spans a fused run needs
(docs/device_plane.md, "Structural indexing", says which pipelines
reach it).  `emit_delim_spans` is the delimiter mode's span step on the
host; `processor_parse_delimiter_tpu` uses the native walk instead.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

MODE_JSON = "json"
MODE_DELIM = "delim"

_JSON_STRUCT = (0x7B, 0x7D, 0x5B, 0x5D, 0x3A, 0x2C)  # { } [ ] : ,
_WS = (0x20, 0x09, 0x0A, 0x0D)
_BS = 0x5C
_QUOTE = 0x22


def _pack16(bits, xp):
    """bool [B, L] -> int32 [B, ceil(L/16)] little-endian bit words."""
    B, L = bits.shape
    W = (L + 15) // 16
    pad = W * 16 - L
    if pad:
        bits = xp.concatenate(
            [bits, xp.zeros((B, pad), dtype=bool)], axis=1)
    weights = (xp.ones((), dtype=xp.int32) << xp.arange(16, dtype=xp.int32))
    return xp.sum(bits.reshape(B, W, 16).astype(xp.int32) * weights, axis=2)


def _index_core(rows, lengths, mode: str, sep: int, xp, scan_max):
    """Shared mask math: rows u8 [B, L], lengths i32 [B] ->
    (in_string, structural, escaped, quote) bool [B, L]."""
    B, L = rows.shape
    pos = xp.arange(L, dtype=xp.int32)[None, :] + xp.zeros(
        (B, 1), dtype=xp.int32)
    valid = pos < lengths.astype(xp.int32)[:, None]
    quote = (rows == _QUOTE) & valid
    if mode == MODE_JSON:
        bs = (rows == _BS) & valid
        # last non-backslash position at or before i (associative max-scan)
        lnb = scan_max(xp.where(~bs, pos, xp.int32(-1)))
        # run of backslashes ending at i-1 has length (i-1) - lnb(i-1);
        # odd run ⇒ the (non-backslash) byte at i is escaped
        run_prev = xp.concatenate(
            [xp.zeros((B, 1), dtype=xp.int32),
             (pos - lnb)[:, :-1]], axis=1)
        escaped = (~bs) & ((run_prev % 2) == 1) & valid
        st = xp.zeros((B, L), dtype=bool)
        for c in _JSON_STRUCT:
            st = st | (rows == c)
        st = st & valid
    else:
        escaped = xp.zeros((B, L), dtype=bool)
        st = (rows == sep) & valid
    q_real = quote & ~escaped
    in_string = (xp.cumsum(q_real.astype(xp.int32), axis=1) % 2) == 1
    in_string = in_string & valid
    structural = st & ~in_string
    return in_string, structural, escaped, q_real


def struct_index_numpy(rows: np.ndarray, lengths: np.ndarray,
                       mode: str = MODE_JSON, sep: int = 0x2C
                       ) -> Tuple[np.ndarray, ...]:
    """Numpy twin: packed int32 [B, W16] masks (in_string, structural,
    escaped, quote) — the degraded-tier index and the device reference."""
    rows = np.asarray(rows, dtype=np.uint8)
    lengths = np.asarray(lengths, dtype=np.int32)

    def scan_max(a):
        return np.maximum.accumulate(a, axis=1)

    masks = _index_core(rows, lengths, mode, sep, np, scan_max)
    return tuple(_pack16(m, np) for m in masks)


def unpack16(words, L: int) -> np.ndarray:
    """int32 [B, W16] -> bool [B, L] (inverse of the kernel packing)."""
    words = np.asarray(words)
    bits = (words[:, :, None] >> np.arange(16)) & 1
    return bits.reshape(words.shape[0], -1)[:, :L].astype(bool)


def native_masks_as_words16(mask_u64: np.ndarray) -> np.ndarray:
    """uint64 [n, W] native masks -> int32 [n, W*4] 16-bit words (the
    device packing), for differential comparison on little-endian hosts."""
    u16 = mask_u64.view(np.uint16).reshape(mask_u64.shape[0], -1)
    return u16.astype(np.int32)


def build_index_fn(mode: str, sep: int):
    """Returns jit-able f(rows u8 [B,L], lengths i32 [B]) -> 4 packed
    int32 [B, W16] masks.  Pure jnp — one fused dispatch per geometry."""
    import jax.numpy as jnp
    from jax.lax import associative_scan

    def scan_max(a):
        return associative_scan(jnp.maximum, a, axis=1)

    def index(rows, lengths):
        masks = _index_core(rows, lengths.astype(jnp.int32), mode, sep,
                            jnp, scan_max)
        return tuple(_pack16(m, jnp) for m in masks)

    return index


class StructIndexKernel:
    """Owns the jitted structural-index function for one mode.

    jit caches per (B, L) geometry; `index_batch` quantises shapes through
    ops.device_batch buckets so a batch-ring slot is ONE dispatch (the
    device test asserts dispatch_count).  `donated_call` mirrors the
    loongstream donated-buffer contract: ring-slot staging buffers are
    transient, so their device copies are donated to the outputs.
    """

    def __init__(self, mode: str = MODE_JSON, sep: int = 0x2C):
        from ..compile_watch import watched_jit
        self.mode = mode
        self.sep = sep
        self._fn = watched_jit(build_index_fn(mode, sep), "struct_index")
        self._fn_donated = None
        self.dispatch_count = 0

    def __call__(self, rows, lengths):
        self.dispatch_count += 1
        return self._fn(rows, lengths)

    def donated_call(self, rows, lengths):
        from .field_extract import donation_supported
        if not donation_supported():
            return self(rows, lengths)
        if self._fn_donated is None:
            from ..compile_watch import watched_jit
            self._fn_donated = watched_jit(
                build_index_fn(self.mode, self.sep), "struct_index",
                donate_argnums=(0, 1))
        self.dispatch_count += 1
        return self._fn_donated(rows, lengths)

    def index_batch(self, arena: np.ndarray, offsets: np.ndarray,
                    lengths: np.ndarray):
        """Pack a columnar group into a device batch (the loongstream slot
        geometry) and index it in one dispatch.  Returns (masks tuple of
        numpy int32 [n, W16], L) — rows beyond n are padding."""
        import jax

        from ..device_batch import pack_rows, pick_length_bucket
        n = len(offsets)
        L = pick_length_bucket(int(lengths.max()) if n else 1)
        if L is None:
            return None
        batch = pack_rows(arena, offsets.astype(np.int64),
                          np.asarray(lengths, dtype=np.int32), L)
        out = self.donated_call(batch.rows, batch.lengths)
        out = jax.device_get(out)
        return tuple(np.asarray(m)[:n] for m in out), L


# ---------------------------------------------------------------------------
# Span emission from the index (quote-mode delimiter).
#
# Vectorised over the whole batch for the CLEAN subset — rows whose quotes
# all delimit whole fields (RFC4180 shape: quote at a field edge, no
# doubled quotes, even parity).  Everything else is flagged deviant and
# handled by the caller's counted per-row fallback; the native fused walk
# (`lct_delim_struct_parse`) handles every shape without fallback.
# ---------------------------------------------------------------------------


def emit_delim_spans(arena: np.ndarray, offsets: np.ndarray,
                     lengths: np.ndarray, quote_bits: np.ndarray,
                     sep_bits: np.ndarray, F: int):
    """arena u8; offsets i64 / lengths i32 [n]; quote_bits / sep_bits
    bool [n, L] row-local (sep_bits = structural mask: separators outside
    the quote-parity in-string interpretation).  Returns (cap_off [n,F]
    i32, cap_len [n,F] i32, nfields [n] i32, deviant bool [n])."""
    n, L = quote_bits.shape
    lengths = np.asarray(lengths, dtype=np.int32)
    offsets = np.asarray(offsets, dtype=np.int64)
    cap_off = np.zeros((n, F), dtype=np.int32)
    cap_len = np.full((n, F), -1, dtype=np.int32)

    # deviance: odd quote parity, or any quote not adjacent to a field
    # boundary (row edge / real separator), or more fields than F (the
    # join rule rewrites bytes, which the span-only path cannot express)
    qcount = quote_bits.sum(axis=1)
    row_idx = np.arange(n, dtype=np.int64)
    last = np.maximum(lengths.astype(np.int64) - 1, 0)
    prev_sep = np.zeros_like(quote_bits)
    prev_sep[:, 1:] = sep_bits[:, :-1]
    next_sep = np.zeros_like(quote_bits)
    next_sep[:, :-1] = sep_bits[:, 1:]
    at_start = np.zeros_like(quote_bits)
    at_start[:, 0] = True
    at_end = np.zeros_like(quote_bits)
    at_end[row_idx, last] = lengths > 0
    boundary_ok = at_start | at_end | prev_sep | next_sep
    deviant = (qcount % 2 == 1) | (quote_bits & ~boundary_ok).any(axis=1)

    scount = sep_bits.sum(axis=1).astype(np.int32)
    nfields = np.where(lengths >= 0, scount + 1, 0).astype(np.int32)
    deviant = deviant | (nfields > F)

    # k-th separator position per row (k < F-1), via the CSR over nonzero
    srow, spos = np.nonzero(sep_bits)
    starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(scount, out=starts[1:])
    edges = np.full((n, F + 1), -1, dtype=np.int64)
    edges[:, 0] = 0
    for k in range(1, F):
        has = scount >= k
        idx = starts[:-1][has] + (k - 1)
        edges[has, k] = spos[idx] + 1 if len(srow) else -1
    # exclusive end per field: next separator or row end
    for k in range(F):
        start = edges[:, k]
        have = (start >= 0) & (k < nfields)
        nxt = np.where((k + 1 <= F - 1) & (edges[:, k + 1] > 0),
                       edges[:, k + 1] - 1, lengths.astype(np.int64))
        end = np.where(k == nfields - 1, lengths.astype(np.int64), nxt)
        start = np.where(have, start, 0)
        end = np.maximum(np.where(have, end, 0), start)
        # quoted-field strip: first byte is a quote (cleanliness has
        # already guaranteed the matching closing quote at the far edge)
        first_q = np.zeros(n, dtype=bool)
        nonempty = have & (end > start)
        if nonempty.any():
            first_q[nonempty] = quote_bits[row_idx[nonempty],
                                           start[nonempty]]
        strip = first_q & (end - start >= 2)
        start = start + strip
        end = end - strip
        cap_off[:, k] = np.where(have, offsets + start, 0).astype(np.int32)
        cap_len[:, k] = np.where(have, end - start, -1).astype(np.int32)
    return cap_off, cap_len, nfields, np.asarray(deviant, dtype=bool)
