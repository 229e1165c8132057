"""Pallas-fused Tier-1 field extraction.

The XLA-path kernel (field_extract.py) expresses the segment walk as ~#ops
masked reductions over the full [B, L] tensor; whether they collapse into
one HBM pass depends on XLA's fuser.  This wrapper removes that bet: the
batch is gridded into [bB, L] row blocks, each block is DMA'd into VMEM
ONCE, and the ENTIRE program — membership masks, literal shift-compares,
forward walk, pivot check, reverse walk — runs on the resident tile.  HBM
traffic drops from O(#ops · B · L) worst-case to exactly one read of the
rows plus the tiny span outputs.

The kernel BODY is the same `build_extract_core` walk used by the XLA path,
so every differential-fuzz guarantee transfers; the suite runs both paths
against each other (tests/test_pallas_kernel.py).

Reference hot loop being replaced: ProcessorParseRegexNative.cpp:186-253.
Mosaic constraints honoured (pallas_guide.md): 2D iota, [B,1] state
columns, u8 tiles with sublane-32 blocks, lane dim = L (multiple of 128
via device_batch LENGTH_BUCKETS), scalar-free control flow.  The u8 tile
is widened to i32 on load: the v5e VPU has no 8-bit compare ("Target does
not support this comparison" on `arith.cmpi ... xi8`), so every class and
literal test runs on 32-bit lanes.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..regex.program import SegmentProgram
from .field_extract import build_extract_core, walk_masks

# The scoped VMEM the kernel is compiled against (v5e's default, passed
# explicitly so another generation's default cannot change what fits), and
# the share of it the block chooser may fill with the walk's [bB, L] arrays;
# the rest is the double-buffered u8 input block and Mosaic's own scratch.
_VMEM_LIMIT = 16 * 1024 * 1024
_VMEM_BUDGET = 12 * 1024 * 1024
# [bB, L] i32 temporaries live at once besides the tile and the masks
# (iota, a shifted literal operand, the where/min/max reduction operands)
_I32_TEMPS = 8
_MIN_BLOCK_ROWS = 32              # the u8 input tile packs 32 sublanes


def _pick_block_rows(B: int, L: int, n_masks: int) -> int:
    """Largest power-of-two row block whose estimated working set fits
    the budget.

    Every [bB, L] array in the body is counted 32-bit: the tile is
    widened to i32 on load and a mask is as wide as the compare that made
    it.  Working set ≈ 4·bB·L·(1 tile + n_masks + _I32_TEMPS).  The
    estimate only picks the block, and it is an upper bound — Mosaic does
    not keep every mask live at once: at the 32-row floor a 35-mask
    program compiled at L = 4096 on a v5e, where the estimate reads 22 MiB
    (scripts/pallas_equivalence.py; the Apache program has 8 masks).  What
    truly does not fit, Mosaic refuses against ``_VMEM_LIMIT`` in its own
    words.  Both B (≥256) and the result are powers of two, so the block
    always divides the batch exactly — no ragged edge to mask.
    """
    per_row = 4 * L * (1 + n_masks + _I32_TEMPS)
    bB = 512
    while bB > _MIN_BLOCK_ROWS and bB * per_row > _VMEM_BUDGET:
        bB //= 2
    return min(bB, B)


def build_extract_fn_pallas(program: SegmentProgram,
                            interpret: bool = False):
    """Returns jit-able f(rows u8 [B,L], lengths i32 [B]) ->
    (ok bool [B], cap_off i32 [B,C], cap_len i32 [B,C]).

    Compiled Mosaic by default; ``interpret=True`` is for the CPU tests
    (differential fuzzing) and is never inferred from the backend — a
    production engine that asks for Pallas off-chip fails loudly."""
    core = build_extract_core(program)
    ncaps = max(program.num_caps, 1)
    span_c, count_c, lits = walk_masks(program)
    n_masks = len(span_c | count_c) + len(lits)

    def kernel(rows_ref, len_ref, ok_ref, off_ref, cl_ref):
        rows = rows_ref[...].astype(jnp.int32)
        lens = len_ref[...]
        ok, off, length = core(rows, lens)
        ok_ref[...] = ok.astype(jnp.int32)
        off_ref[...] = off
        cl_ref[...] = length

    def extract(rows: jnp.ndarray, lengths: jnp.ndarray):
        B, L = rows.shape
        bB = _pick_block_rows(B, L, n_masks)
        grid = (B // bB,)
        row_block = pl.BlockSpec((bB, L), lambda i: (i, 0))
        col1 = pl.BlockSpec((bB, 1), lambda i: (i, 0))
        cap_block = pl.BlockSpec((bB, ncaps), lambda i: (i, 0))
        ok2, off, length = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[row_block, col1],
            out_specs=[col1, cap_block, cap_block],
            out_shape=[
                jax.ShapeDtypeStruct((B, 1), jnp.int32),
                jax.ShapeDtypeStruct((B, ncaps), jnp.int32),
                jax.ShapeDtypeStruct((B, ncaps), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            # the device op's name (`%extract.N` on the profiler's XLA Ops
            # line): stated, so that renaming the jitted function around
            # it (compile_watch.watched_jit) cannot move it
            name="extract",
        )(rows, lengths.astype(jnp.int32)[:, None])
        return ok2[:, 0] != 0, off, length

    return extract


class PallasExtractKernel:
    """Drop-in sibling of ExtractKernel running the fused Pallas path."""

    family = "extract_pallas"

    def __init__(self, program: SegmentProgram, interpret: bool = False):
        from ..compile_watch import watched_jit
        from ..packed_io import packed_entry, span_columns
        self.program = program
        extract = build_extract_fn_pallas(program, interpret=interpret)
        self._fn = watched_jit(extract, self.family)
        #: the streaming path's entry (ops/packed_io.py): the slice, the
        #: bitcast and the concatenate are XLA operations around the same
        #: Pallas call in the one module, ``unpack`` splits the result
        self.packed_call, self.unpack = packed_entry(
            extract, span_columns(program.num_caps), self.family)

    def __call__(self, rows, lengths
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        return self._fn(rows, lengths)

    @property
    def num_caps(self) -> int:
        return self.program.num_caps
