"""Host-tier Tier-1 execution: serialize SegmentPrograms for the C++ walker.

On a CPU backend (an explicit pin: tests, CPU drives) the XLA:CPU
emulation of the masked-reduction kernel is an order of magnitude slower
than a direct scalar walk, and on a chip a small batch loses to the fixed
dispatch round trip, so there the engine routes parse_batch to `lct_t1_exec`
(native/loongcollector_native.cpp) — the same compiled IR, executed
per-row, mirroring ops/kernels/field_extract.py op-for-op.  The reference's
equivalent hot loop is likewise native C++
(core/plugin/processor/ProcessorParseRegexNative.cpp:186-253).

Differential bit-identity with the device kernel is enforced by
tests/test_native_t1.py over the generative fuzz corpus.
"""

from __future__ import annotations

import ctypes
from typing import Dict, List, Optional, Tuple

import numpy as np

from ... import native as native_mod
from .program import (INF, Alt, CapEnd, CapStart, FixedSpan, Lit, Optional_,
                      SegmentProgram, Span)

MAX_CAPS = 32     # kT1MaxCaps in the C++ executor
MAX_CLASSES = 64  # kT1MaxClasses in the C++ executor


class NativeUnsupported(Exception):
    """Program cannot run on the native tier (too many caps, lib absent)."""


class _LitTable:
    def __init__(self) -> None:
        self._idx: Dict[bytes, int] = {}
        self.blob = bytearray()
        self.offs: List[int] = []
        self.lens: List[int] = []

    def add(self, data: bytes) -> int:
        got = self._idx.get(data)
        if got is not None:
            return got
        idx = len(self.offs)
        self._idx[data] = idx
        self.offs.append(len(self.blob))
        self.lens.append(len(data))
        self.blob.extend(data)
        return idx


def _ser_ops(ops, words: List[int], lits: _LitTable, reverse: bool) -> None:
    for op in ops:
        if isinstance(op, Lit):
            # suffix ops store literal bytes pre-reversed; the executor
            # memcmps the FORWARD spelling at (cur - k), so un-reverse here
            data = op.data[::-1] if reverse else op.data
            words.extend([0, lits.add(data)])
        elif isinstance(op, Span):
            words.extend([1, op.class_id, op.min_len,
                          -1 if op.max_len == INF else op.max_len,
                          1 if op.lazy else 0])
        elif isinstance(op, FixedSpan):
            words.extend([2, op.class_id, op.n])
        elif isinstance(op, CapStart):
            words.extend([3, op.cap_id])
        elif isinstance(op, CapEnd):
            words.extend([4, op.cap_id])
        elif isinstance(op, Optional_):
            body: List[int] = []
            _ser_ops(op.body, body, lits, reverse)
            words.extend([5, len(body)])
            words.extend(body)
        elif isinstance(op, Alt):
            words.extend([6, len(op.branches)])
            for branch in op.branches:
                body = []
                _ser_ops(branch, body, lits, reverse)
                words.append(len(body))
                words.extend(body)
        else:  # pragma: no cover
            raise NativeUnsupported(f"op {op!r}")


def serialize_program(program: SegmentProgram
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray, np.ndarray, int]:
    """Returns (words i32, class_bitmaps u8 [K,256], lit_blob u8,
    lit_offs i32, lit_lens i32, num_caps)."""
    ncaps = max(program.num_caps, 1)
    if ncaps > MAX_CAPS:
        raise NativeUnsupported(f"{ncaps} captures > {MAX_CAPS}")
    if len(program.classes) > MAX_CLASSES:
        # kT1MaxClasses: the executor rejects such programs at call time
        # (rc=-1); refusing to build keeps the engine on its fallback tier
        raise NativeUnsupported(
            f"{len(program.classes)} classes > {MAX_CLASSES}")
    lits = _LitTable()
    words: List[int] = [1, ncaps]

    prefix: List[int] = []
    _ser_ops(program.ops, prefix, lits, reverse=False)
    words.append(len(prefix))
    words.extend(prefix)

    if program.pivot is not None:
        p = program.pivot
        words.extend([1, p.class_id, p.min_len,
                      -1 if p.max_len == INF else p.max_len,
                      1 if p.lazy else 0])
    else:
        words.append(0)

    suffix: List[int] = []
    if program.suffix_ops:
        _ser_ops(program.suffix_ops, suffix, lits, reverse=True)
    words.append(len(suffix))
    words.extend(suffix)

    if program.pivot2 is not None:
        p2 = program.pivot2
        words.extend([1, p2.class_id, p2.min_len,
                      -1 if p2.max_len == INF else p2.max_len,
                      1 if p2.lazy else 0])
    else:
        words.append(0)

    mid: List[int] = []
    if program.mid_ops:
        _ser_ops(program.mid_ops, mid, lits, reverse=False)
    words.append(len(mid))
    words.extend(mid)

    words.append(len(program.split_caps))
    words.extend(program.split_caps)
    words.append(len(program.mid_end_caps))
    words.extend(program.mid_end_caps)

    bitmaps = np.stack([c.mask for c in program.classes]).astype(np.uint8) \
        if program.classes else np.zeros((0, 256), np.uint8)
    return (np.array(words, dtype=np.int32),
            np.ascontiguousarray(bitmaps),
            np.frombuffer(bytes(lits.blob) or b"\0", dtype=np.uint8).copy(),
            np.array(lits.offs or [0], dtype=np.int32),
            np.array(lits.lens or [0], dtype=np.int32),
            ncaps)


def _bind(lib) -> None:
    if getattr(lib, "_t1_bound", False):
        return
    u8p = ctypes.c_void_p      # raw addresses (see native.py binding note)
    i32p = ctypes.c_void_p
    i64p = ctypes.c_void_p
    lib.lct_t1_exec.restype = ctypes.c_int64
    lib.lct_t1_exec.argtypes = [
        u8p, ctypes.c_int64, i64p, i32p, ctypes.c_int64,
        i32p, ctypes.c_int64, u8p, ctypes.c_int64,
        u8p, i32p, i32p, ctypes.c_int64,
        u8p, i32p, i32p]
    lib._t1_bound = True


class NativeT1Executor:
    """One serialized program + the ctypes call, shaped like the device
    path's output: (ok bool [N], cap_off i32 [N,C] arena-ABSOLUTE,
    cap_len i32 [N,C], len -1 = absent)."""

    def __init__(self, program: SegmentProgram):
        lib = native_mod.get_lib()
        if lib is None or not hasattr(lib, "lct_t1_exec"):
            raise NativeUnsupported("native library unavailable")
        _bind(lib)
        self._lib = lib
        (self._words, self._bitmaps, self._blob, self._loffs, self._llens,
         self.num_caps) = serialize_program(program)

    def __call__(self, arena: np.ndarray, offsets: np.ndarray,
                 lengths: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        arena = np.ascontiguousarray(arena, dtype=np.uint8)
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        lengths = np.ascontiguousarray(lengths, dtype=np.int32)
        n = len(offsets)
        C = self.num_caps
        # one arena carve instead of three mmap-class allocations: the
        # outputs live as long as the group's columns, so they cannot be
        # pooled, but they CAN share one block (pipeline-e2e hot path)
        span = n * C * 4
        blk = np.empty(span * 2 + n, dtype=np.uint8)
        cap_off = blk[:span].view(np.int32).reshape(n, C)
        cap_len = blk[span:span * 2].view(np.int32).reshape(n, C)
        ok = blk[span * 2:]
        u8 = native_mod._u8
        i32 = native_mod._i32
        i64 = native_mod._i64
        rc = self._lib.lct_t1_exec(
            u8(arena), len(arena), i64(offsets), i32(lengths), n,
            i32(self._words), len(self._words),
            u8(self._bitmaps), len(self._bitmaps),
            u8(self._blob), i32(self._loffs), i32(self._llens),
            len(self._loffs),
            u8(ok), i32(cap_off), i32(cap_len))
        if rc != 0:
            raise NativeUnsupported(f"lct_t1_exec rc={rc}")
        # zero-copy reinterpret: the executor writes strictly 0/1
        return ok.view(np.bool_), cap_off, cap_len


def try_build(program: SegmentProgram) -> Optional[NativeT1Executor]:
    try:
        return NativeT1Executor(program)
    except NativeUnsupported:
        return None
