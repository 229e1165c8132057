"""ctypes bridge to the C++ native data plane (native/).

Loads libloongcollector_native.so (building it once with the repo's
Makefile when it is absent).  A library that cannot be built or loaded is
an error: every entry point does have a pure-numpy/Python twin, but those
are the references the equivalence gates compare against, reached only by
the explicit ``LOONG_DISABLE_NATIVE`` switch — never by a build that
failed quietly.

Reference parity: the reference's equivalents are C++ (LogFileReader line
alignment, the batch staging copy, core/protobuf/sls/LogGroupSerializer).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import NamedTuple, Optional, Tuple

import numpy as np

from .utils.logger import get_logger

log = get_logger("native")

_lib = None
_load_lock = threading.Lock()
_load_attempted = False
#: the failure of this process's one load attempt, re-raised on later calls
_load_error: Optional["NativeLibraryError"] = None

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libloongcollector_native.so")


def _so_path() -> str:
    """LOONG_NATIVE_LIB points the bridge at an alternate build — the
    sanitizer harness (scripts/sanitize.sh) loads its ASan/TSan
    instrumented library without touching the release artifact."""
    return os.environ.get("LOONG_NATIVE_LIB") or _SO_PATH


class NativeLibraryError(RuntimeError):
    """The native library could not be built or loaded."""


def _build() -> None:
    """`make -C native`; raises NativeLibraryError with the compiler's
    own words when it fails."""
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR, "-s"], check=True,
                       timeout=120, capture_output=True)
    except subprocess.CalledProcessError as e:
        raise NativeLibraryError(
            "native build failed:\n"
            + e.stderr.decode(errors="replace")[-2000:]) from e
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeLibraryError(f"native build failed: {e!r}") from e
    if not os.path.exists(_SO_PATH):
        raise NativeLibraryError(f"native build left no {_SO_PATH}")


def _cdll(so_path: str, handle=ctypes.CDLL) -> ctypes.CDLL:
    try:
        return handle(so_path)
    except OSError as e:
        raise NativeLibraryError(
            f"failed to load native library {so_path}: {e}") from e


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, or None under LOONG_DISABLE_NATIVE.  One load
    (and at most one build) attempt per process: a failure is kept and
    re-raised by every later call."""
    global _lib, _load_attempted, _load_error
    if not _load_attempted:
        with _load_lock:
            if not _load_attempted:
                try:
                    _lib = _load()
                except NativeLibraryError as e:
                    _load_error = e
                _load_attempted = True
    if _load_error is not None:
        raise _load_error
    return _lib


def _load() -> Optional[ctypes.CDLL]:
    if os.environ.get("LOONG_DISABLE_NATIVE"):
        return None
    so_path = _so_path()
    overridden = so_path != _SO_PATH
    if not os.path.exists(so_path):
        # an explicit override must load exactly what it names — never
        # fall back to (or rebuild over) the release artifact
        if overridden:
            raise NativeLibraryError(
                f"LOONG_NATIVE_LIB={so_path} does not exist")
        _build()
    lib = _cdll(so_path)
    if not overridden and (
            not hasattr(lib, "lct_t1_exec")
            or not hasattr(lib, "lct_ndjson_serialize")
            or not hasattr(lib, "lct_struct_index")
            or not hasattr(lib, "lct_group_reduce")
            or not hasattr(lib, "lct_ndjson_serialize_append")
            or not hasattr(lib, "lct_timestamp_column")):
        # stale build predating the newest entry point: rebuild + reload
        _build()
        lib = _cdll(so_path)
    # pointer params bind as c_void_p and calls pass raw addresses
    # (arr.ctypes.data): ctypes POINTER casts cost ~2 us each and the
    # hot wrappers pass ~20 pointers per group
    u8p = ctypes.c_void_p
    i32p = ctypes.c_void_p
    i64p = ctypes.c_void_p
    lib.lct_split_lines.restype = ctypes.c_int64
    lib.lct_split_lines.argtypes = [u8p, ctypes.c_int64, ctypes.c_uint8,
                                    ctypes.c_int64, i32p, i32p]
    lib.lct_pack_rows.restype = None
    lib.lct_pack_rows.argtypes = [u8p, ctypes.c_int64, i64p, i32p,
                                  ctypes.c_int64, ctypes.c_int64, u8p]
    if hasattr(lib, "lct_timestamp_column"):
        # this one function through a PyDLL handle of the same library: the
        # call keeps the interpreter lock for the 0.06 ms a group it takes
        # (far under the 5 ms switch interval) instead of letting go of it
        # and queueing behind the reader's and the sender's threads to get
        # it back.  Measured against the CDLL handle (PERF.md section 6,
        # PR 37): the stage a third shorter in the cell, forty times beside
        # a thread that runs bytecode; the cell's rate 0.8 % lower
        lib.keeps_lock = _cdll(so_path, ctypes.PyDLL)
        lib.lct_timestamp_column = lib.keeps_lock.lct_timestamp_column
        lib.lct_timestamp_column.restype = ctypes.c_int64
        lib.lct_timestamp_column.argtypes = (
            [u8p, ctypes.c_int64, i32p, ctypes.c_int64, i32p, ctypes.c_int64,
             ctypes.c_int64, i64p, ctypes.c_int64, ctypes.c_int64,
             ctypes.c_int64, u8p, i64p, i64p, i64p, ctypes.c_int64, i64p]
            + [i64p] * 4)
    lib.lct_json_extract.restype = None
    lib.lct_json_extract.argtypes = [u8p, ctypes.c_int64, i64p, i32p,
                                     ctypes.c_int64, u8p, i32p,
                                     ctypes.c_int64, i32p, i32p,
                                     u8p, u8p]
    lib.lct_sls_serialize.restype = ctypes.c_int64
    lib.lct_sls_serialize.argtypes = [u8p, ctypes.c_int64, i64p,
                                      ctypes.c_int64, ctypes.c_int64,
                                      u8p, i32p, i32p, i32p,
                                      u8p, ctypes.c_int64]
    if hasattr(lib, "lct_sls_serialize_strided"):
        lib.lct_sls_serialize_strided.restype = ctypes.c_int64
        lib.lct_sls_serialize_strided.argtypes = [
            u8p, ctypes.c_int64, i64p, ctypes.c_int64, ctypes.c_int64,
            u8p, i32p, i32p, i32p, ctypes.c_int64, ctypes.c_int64,
            u8p, ctypes.c_int64]
    if hasattr(lib, "lct_ndjson_serialize"):
        lib.lct_ndjson_serialize.restype = ctypes.c_int64
        lib.lct_ndjson_serialize.argtypes = [
            u8p, ctypes.c_int64, i64p, ctypes.c_int64, ctypes.c_int64,
            u8p, i32p, i32p, i32p, ctypes.c_int64, ctypes.c_int64,
            u8p, ctypes.c_int64, ctypes.c_int32,
            u8p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            u8p, ctypes.c_int64, u8p, ctypes.c_int64]
    if hasattr(lib, "lct_append_file"):
        lib.lct_append_file.restype = ctypes.c_int64
        lib.lct_append_file.argtypes = [ctypes.c_char_p, u8p,
                                        ctypes.c_int64]
    if hasattr(lib, "lct_ndjson_serialize_append"):
        lib.lct_ndjson_serialize_append.restype = ctypes.c_int64
        lib.lct_ndjson_serialize_append.argtypes = (
            [ctypes.c_char_p] + lib.lct_ndjson_serialize.argtypes + [i64p])
    if hasattr(lib, "lct_struct_index"):
        lib.lct_struct_index.restype = None
        lib.lct_struct_index.argtypes = [
            u8p, ctypes.c_int64, i64p, i32p, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_uint8, ctypes.c_uint8,
            ctypes.c_int64, u8p, u8p, u8p, u8p]
    if hasattr(lib, "lct_json_struct_parse"):
        lib.lct_json_struct_parse.restype = ctypes.c_int64
        lib.lct_json_struct_parse.argtypes = [
            u8p, ctypes.c_int64, i64p, i32p, ctypes.c_int64,
            u8p, i32p, ctypes.c_int64, i32p, i32p, u8p,
            u8p, ctypes.c_int64,
            i32p, i32p, i32p, i32p, i32p, ctypes.c_int64, i64p]
    if hasattr(lib, "lct_group_reduce"):
        lib.lct_group_reduce.restype = ctypes.c_int64
        lib.lct_group_reduce.argtypes = [
            u8p, ctypes.c_int64,
            i64p, i64p, i32p, i64p, i32p,
            ctypes.c_int64, ctypes.c_int64,
            ctypes.c_double, ctypes.c_int64,
            i32p, i32p, u8p, i64p, u8p, u8p, u8p,
            i64p, ctypes.c_int64]
    if hasattr(lib, "lct_delim_struct_parse"):
        lib.lct_delim_struct_parse.restype = ctypes.c_int64
        lib.lct_delim_struct_parse.argtypes = [
            u8p, ctypes.c_int64, i64p, i32p, ctypes.c_int64,
            ctypes.c_uint8, ctypes.c_uint8, ctypes.c_int64,
            i32p, i32p, i32p, u8p, ctypes.c_int64, i64p]
    for fn in ("lct_lz4_bound", "lct_lz4_compress", "lct_lz4_decompress",
               "lct_snappy_bound", "lct_snappy_compress",
               "lct_snappy_uncompressed_len", "lct_snappy_decompress"):
        f = getattr(lib, fn, None)
        if f is None:      # stale .so predating the codecs: rebuild once
            continue
        f.restype = ctypes.c_int64
        f.argtypes = ([ctypes.c_int64] if fn.endswith("bound")
                      else [u8p, ctypes.c_int64, u8p, ctypes.c_int64]
                      if not fn.endswith("uncompressed_len")
                      else [u8p, ctypes.c_int64])
    log.info("native library loaded: %s", so_path)
    return lib


def _u8(a: np.ndarray) -> int:
    return a.ctypes.data


def _i32(a: np.ndarray) -> int:
    return a.ctypes.data


def _i64(a: np.ndarray) -> int:
    return a.ctypes.data


# ---------------------------------------------------------------------------
# wrappers (None return ⇒ caller should use its fallback)
# ---------------------------------------------------------------------------


_split_scratch = threading.local()


def split_lines(seg: np.ndarray, sep: int, base_offset: int
                ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    lib = get_lib()
    if lib is None or len(seg) == 0:
        return None
    seg = np.ascontiguousarray(seg)
    # worst case is one line per byte, so the span buffers are chunk-sized;
    # reuse a per-thread scratch instead of mapping/unmapping megabytes per
    # chunk and return right-sized copies (a few KB for real line counts)
    cap = len(seg) + 1
    sc = getattr(_split_scratch, "bufs", None)
    if sc is None or len(sc[0]) < cap:
        sc = (np.empty(cap, dtype=np.int32), np.empty(cap, dtype=np.int32))
        _split_scratch.bufs = sc
    offs, lens = sc
    n = lib.lct_split_lines(_u8(seg), len(seg), sep, base_offset,
                            _i32(offs), _i32(lens))
    return offs[:n].copy(), lens[:n].copy()


def pack_rows(arena: np.ndarray, offsets: np.ndarray, lengths: np.ndarray,
              L: int, B: int,
              out: Optional[np.ndarray] = None) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    arena = np.ascontiguousarray(arena)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int32)
    n = len(offsets)
    if out is not None:
        # batch-ring reuse: the C packer fully writes rows [0, n) (memcpy +
        # tail memset) but never touches the padding rows [n, B), which may
        # hold a previous generation's bytes — re-zero only those
        rows = out
        if n < B:
            rows[n:].fill(0)
    else:
        rows = np.zeros((B, L), dtype=np.uint8)
    lib.lct_pack_rows(_u8(arena), len(arena), _i64(offsets), _i32(lengths),
                      n, L, _u8(rows))
    return rows


class TimestampColumn(NamedTuple):
    """What one `lct_timestamp_column` call found: the present rows it saw,
    the rows it stored, and (int64, views of the call's one scratch array)
    the rows it hands to the per-row path, the rows whose minute the memo
    lacks, and those minutes' distinct keys, ascending."""
    present: int
    stored: int
    rest: np.ndarray
    pending: np.ndarray
    missing: np.ndarray


def timestamp_column(arena: np.ndarray, offsets: np.ndarray,
                     lengths: np.ndarray, rows: Optional[np.ndarray],
                     min_present: int, width: int, table: np.ndarray,
                     weights: np.ndarray, memo_keys: np.ndarray,
                     memo_seconds: np.ndarray, timestamps: np.ndarray
                     ) -> Optional[TimestampColumn]:
    """The timestamp processor's column path over one group in ONE native
    call (processor/parse_timestamp.py: the plan's `native_table` [width *
    256] uint8 and `native_weights` [width, 2] int64, the minute memo as two
    sorted int64 arrays).  `offsets` / `lengths` are a field's int32 columns
    as `ColumnarLogs` holds them, strided or not; proven stamps are stored
    into `timestamps` (int64, contiguous) in place.  `rows`: None for the
    whole group — then a group with fewer than `min_present` present rows is
    declined — or the int64 rows a first call left pending.

    None when the library is unavailable, the columns are not what the call
    reads, or the group is declined: the caller takes its other path, and
    nothing was stored."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lct_timestamp_column"):
        return None
    n = len(timestamps)
    if offsets.dtype != np.int32 or lengths.dtype != np.int32 \
            or offsets.shape != (n,) or lengths.shape != (n,) \
            or timestamps.dtype != np.int64 \
            or not timestamps.flags.c_contiguous \
            or arena.dtype != np.uint8 or not arena.flags.c_contiguous \
            or len(table) != width * 256 or weights.shape != (width, 2) \
            or len(memo_keys) != len(memo_seconds) \
            or (rows is not None and rows.dtype != np.int64):
        return None
    out = np.empty(3 * n + 5, dtype=np.int64)
    base, word = out.ctypes.data, out.itemsize
    rc = lib.lct_timestamp_column(
        arena.ctypes.data, len(arena),
        offsets.ctypes.data, offsets.strides[0],
        lengths.ctypes.data, lengths.strides[0], n,
        None if rows is None else rows.ctypes.data,
        0 if rows is None else len(rows), min_present,
        width, table.ctypes.data, weights.ctypes.data,
        memo_keys.ctypes.data, memo_seconds.ctypes.data, len(memo_keys),
        timestamps.ctypes.data,
        base, base + n * word, base + 2 * n * word, base + 3 * n * word)
    if rc != 0:
        return None
    present, stored, n_rest, n_pending, n_missing = out[3 * n:].tolist()
    return TimestampColumn(present, stored, out[:n_rest],
                           out[n:n + n_pending],
                           out[2 * n:2 * n + n_missing])


def json_extract(arena: np.ndarray, offsets: np.ndarray,
                 lengths: np.ndarray, keys: list):
    """Flat-schema JSON field extraction.  keys: list[bytes] (≤128).
    Returns (offs [F,n] i32, lens [F,n] i32, ok [n] bool, fallback [n] bool)
    or None when the native lib is unavailable."""
    lib = get_lib()
    if lib is None or len(keys) > 128:
        return None
    arena = np.ascontiguousarray(arena)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int32)
    keys_blob = np.frombuffer(b"".join(keys) or b"\0", dtype=np.uint8).copy()
    key_lens = np.array([len(k) for k in keys], dtype=np.int32)
    n = len(offsets)
    F = len(keys)
    out_offs = np.zeros((F, n), dtype=np.int32)
    out_lens = np.full((F, n), -1, dtype=np.int32)
    ok = np.zeros(n, dtype=np.uint8)
    fallback = np.zeros(n, dtype=np.uint8)
    lib.lct_json_extract(_u8(arena), len(arena), _i64(offsets), _i32(lengths),
                         n, _u8(keys_blob), _i32(key_lens), F,
                         _i32(out_offs), _i32(out_lens), _u8(ok),
                         _u8(fallback))
    return out_offs, out_lens, ok.astype(bool), fallback.astype(bool)


STRUCT_MODE_JSON = 0
STRUCT_MODE_DELIM = 1


def struct_index(arena: np.ndarray, offsets: np.ndarray,
                 lengths: np.ndarray, mode: int = STRUCT_MODE_JSON,
                 sep: int = 0x2C, quote: int = 0x22,
                 W: Optional[int] = None):
    """Per-row structural bitmaps (loongstruct stage 1): uint64 [n, W]
    arrays (in_string, structural, escaped, quote) with row-local bit
    positions — the host reference the device twin
    (ops/kernels/struct_index.py) is differentially tested against.
    Returns None when the native library is unavailable."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lct_struct_index"):
        return None
    arena = np.ascontiguousarray(arena)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int32)
    n = len(offsets)
    if W is None:
        W = max(1, (int(lengths.max()) + 63) // 64) if n else 1
    shape = (n, W)
    s_mask = np.zeros(shape, dtype=np.uint64)
    t_mask = np.zeros(shape, dtype=np.uint64)
    e_mask = np.zeros(shape, dtype=np.uint64)
    q_mask = np.zeros(shape, dtype=np.uint64)
    lib.lct_struct_index(_u8(arena), len(arena), _i64(offsets),
                         _i32(lengths), n, mode, sep, quote, W,
                         _u8(s_mask), _u8(t_mask), _u8(e_mask), _u8(q_mask))
    return s_mask, t_mask, e_mask, q_mask


def json_struct_parse(arena: np.ndarray, offsets: np.ndarray,
                      lengths: np.ndarray, keys: list,
                      extra_cap: Optional[int] = None):
    """Structural-index JSON parse (loongstruct stage 2).  keys:
    list[bytes] (<= 128).  Returns (offs [F,n] i32, lens [F,n] i32,
    status [n] u8 (0 parsed / 1 fallback / 2 parsed-with-extras),
    side bytes ndarray (the unescape arena, already right-sized),
    extras tuple of 5 int32 arrays (row, key_off, key_len, val_off,
    val_len)) or None when the native library is unavailable.  Span
    offsets >= len(arena) index into `side` at offset - len(arena)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lct_json_struct_parse") \
            or len(keys) > 128:
        return None
    arena = np.ascontiguousarray(arena)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int32)
    n = len(offsets)
    # side spans encode as arena_len + side_off in an int32
    total = int(lengths.clip(min=0).sum())
    if len(arena) + total >= 2**31 - 16:
        return None
    keys_blob, key_lens = _key_struct(tuple(keys))
    F = len(keys)
    # np.empty throughout: the C side fully writes status and every
    # out_lens slot (-1 default), and only the returned prefixes of the
    # side/extras buffers are exposed — zeroing here costs ~1 MB of
    # stores per group at bench rates for no observable difference
    out_offs = np.empty((F, n), dtype=np.int32)
    out_lens = np.empty((F, n), dtype=np.int32)
    status = np.empty(n, dtype=np.uint8)
    side = np.empty(max(total, 1), dtype=np.uint8)
    if extra_cap is None:
        extra_cap = 4 * n + 64
    extras = tuple(np.empty(extra_cap, dtype=np.int32) for _ in range(5))
    counts = np.zeros(4, dtype=np.int64)
    rc = lib.lct_json_struct_parse(
        _u8(arena), len(arena), _i64(offsets), _i32(lengths), n,
        _u8(keys_blob), _i32(key_lens), F, _i32(out_offs), _i32(out_lens),
        _u8(status), _u8(side), len(side),
        _i32(extras[0]), _i32(extras[1]), _i32(extras[2]),
        _i32(extras[3]), _i32(extras[4]), extra_cap, _i64(counts))
    if rc != 0:
        return None
    e = int(counts[1])
    return (out_offs, out_lens, status, side[: int(counts[0])],
            tuple(a[:e] for a in extras))


def delim_struct_parse(arena: np.ndarray, offsets: np.ndarray,
                       lengths: np.ndarray, sep: int, quote: int,
                       F: int):
    """Structural-index quote-mode delimiter parse: event-major spans
    (offs [n,F] i32, lens [n,F] i32, nfields [n] i32, side bytes).  Span
    offsets >= len(arena) index into `side`.  Returns None when the
    native library is unavailable."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lct_delim_struct_parse") or F <= 0:
        return None
    arena = np.ascontiguousarray(arena)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int32)
    n = len(offsets)
    total = int(lengths.clip(min=0).sum())
    if len(arena) + total >= 2**31 - 16:
        return None
    out_offs = np.zeros((n, F), dtype=np.int32)
    out_lens = np.full((n, F), -1, dtype=np.int32)
    nfields = np.zeros(n, dtype=np.int32)
    side = np.empty(max(total, 1), dtype=np.uint8)
    counts = np.zeros(2, dtype=np.int64)
    rc = lib.lct_delim_struct_parse(
        _u8(arena), len(arena), _i64(offsets), _i32(lengths), n,
        sep, quote, F, _i32(out_offs), _i32(out_lens), _i32(nfields),
        _u8(side), len(side), _i64(counts))
    if rc != 0:
        return None
    return out_offs, out_lens, nfields, side[: int(counts[0])]


def group_reduce(arena: np.ndarray, slots: np.ndarray,
                 key_offs: np.ndarray, key_lens: np.ndarray,
                 val_offs: np.ndarray, val_lens: np.ndarray,
                 hist_base: float = 1.0, n_hist: int = 41):
    """loongagg fold (native substrate): hashed segment identity over
    (window slot, K key spans) + row-order f64 reduction.

    slots i64 [n]; key_offs i64 / key_lens i32 [n, K] (len -1 = absent);
    val_offs i64 / val_lens i32 [n].  Returns (group_id i32 [n] with -1
    marking invalid-value rows, rep_row i32 [G], sum f64 [G], count i64
    [G], min f64 [G], max f64 [G], last f64 [G], hist i64 [G, n_hist]) —
    group ids in first-seen row order, the same partition and the same
    accumulation order as the numpy twin (bit-identical by the
    scripts/agg_equivalence.py gate).  None when the native library is
    unavailable."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lct_group_reduce"):
        return None
    arena = np.ascontiguousarray(arena)
    slots = np.ascontiguousarray(slots, dtype=np.int64)
    key_offs = np.ascontiguousarray(key_offs, dtype=np.int64)
    key_lens = np.ascontiguousarray(key_lens, dtype=np.int32)
    val_offs = np.ascontiguousarray(val_offs, dtype=np.int64)
    val_lens = np.ascontiguousarray(val_lens, dtype=np.int32)
    n = len(slots)
    K = key_offs.shape[1] if key_offs.ndim == 2 else 1
    group_id = np.empty(max(n, 1), dtype=np.int32)
    # start with a small group capacity (the common case: cardinality per
    # batch << rows per batch) and retry once at the n ceiling on -1
    cap = min(n, 4096) or 1
    while True:
        rep_row = np.empty(cap, dtype=np.int32)
        sums = np.empty(cap, dtype=np.float64)
        cnt = np.empty(cap, dtype=np.int64)
        mn = np.empty(cap, dtype=np.float64)
        mx = np.empty(cap, dtype=np.float64)
        last = np.empty(cap, dtype=np.float64)
        hist = np.empty((cap, n_hist), dtype=np.int64)
        rc = lib.lct_group_reduce(
            _u8(arena), len(arena), _i64(slots), _i64(key_offs),
            _i32(key_lens), _i64(val_offs), _i32(val_lens), n, K,
            ctypes.c_double(hist_base), n_hist,
            _i32(group_id), _i32(rep_row), _u8(sums), _i64(cnt),
            _u8(mn), _u8(mx), _u8(last), _i64(hist), cap)
        if rc == -1 and cap < n:
            cap = n
            continue
        if rc < 0:
            return None
        G = int(rc)
        return (group_id[:n], rep_row[:G], sums[:G], cnt[:G], mn[:G],
                mx[:G], last[:G], hist[:G])


_key_cache: dict = {}
_key_cache_lock = threading.Lock()


def _key_struct(keys: tuple) -> Tuple[np.ndarray, np.ndarray]:
    """(keys_blob, key_lens) for a key tuple — serializers call with the
    same schema for every group, so build the arrays once (the per-call
    join+copy was measurable at pipeline-e2e rates)."""
    with _key_cache_lock:
        st = _key_cache.get(keys)
    if st is None:
        # build OUTSIDE the lock (the join is O(schema) work); the
        # setdefault makes a racing double-build harmless
        blob = np.frombuffer(b"".join(keys) or b"\0",
                             dtype=np.uint8).copy()
        lens = np.array([len(k) for k in keys], dtype=np.int32)
        with _key_cache_lock:
            if len(_key_cache) >= 256:    # unbounded schemas must not leak
                _key_cache.clear()
            st = _key_cache.setdefault(keys, (blob, lens))
    return st


def sls_serialize(arena: np.ndarray, timestamps: np.ndarray,
                  keys: list, field_offs: np.ndarray, field_lens: np.ndarray,
                  event_major: bool = False) -> Optional[bytes]:
    """keys: list[bytes] (≤64); field_offs/field_lens: int32 — [F, n]
    field-major by default, [n, F] when event_major=True (the parse-kernel
    output layout, serialized without a transpose)."""
    lib = get_lib()
    if lib is None or len(keys) > 64:
        return None
    if event_major and not hasattr(lib, "lct_sls_serialize_strided"):
        return None
    arena = np.ascontiguousarray(arena)
    timestamps = np.ascontiguousarray(timestamps, dtype=np.int64)
    field_offs = np.ascontiguousarray(field_offs, dtype=np.int32)
    field_lens = np.ascontiguousarray(field_lens, dtype=np.int32)
    keys_blob, key_lens = _key_struct(tuple(keys))
    F = len(keys)
    n = len(timestamps)
    sf, si = (1, F) if event_major else (n, 1)
    # cheap capacity bound: field values live in the arena, so arena_len
    # covers Σvlen unless spans overlap (keep-source cases) — then the call
    # returns -needed and the exact-size retry below handles it
    cap = int(len(arena) + n * (int(key_lens.sum()) + 12 * F + 16) + 64)

    def call(buf, buf_cap):
        if event_major:
            return lib.lct_sls_serialize_strided(
                _u8(arena), len(arena), _i64(timestamps), n, F,
                _u8(keys_blob), _i32(key_lens), _i32(field_offs),
                _i32(field_lens), sf, si, _u8(buf), buf_cap)
        return lib.lct_sls_serialize(
            _u8(arena), len(arena), _i64(timestamps), n, F, _u8(keys_blob),
            _i32(key_lens), _i32(field_offs), _i32(field_lens), _u8(buf),
            buf_cap)

    out = np.empty(cap, dtype=np.uint8)
    written = call(out, cap)
    if written < 0:
        # exact-size retry; the +16 is part of the declared capacity so the
        # 16-byte fast copies stay legal right up to the payload end
        out = np.empty(-written + 16, dtype=np.uint8)
        written = call(out, -written + 16)
        if written < 0:
            return None
    # a view, not bytes: the serializer joins parts once — an extra
    # tobytes here would copy the (larger-than-input) payload again
    return memoryview(out)[:written]


NDJSON_TS_NONE = 0
NDJSON_TS_EPOCH = 1
NDJSON_TS_ISO8601 = 2


def _ndjson_args(arena: np.ndarray, timestamps: np.ndarray,
                 key_frags: tuple, field_offs: np.ndarray,
                 field_lens: np.ndarray, prefix: bytes,
                 prefix_members: bool, ts_frag: bytes, ts_mode: int,
                 ts_first: bool, suffix: bytes, event_major: bool):
    """(lct_ndjson_serialize's arguments before the output buffer, the
    arrays they point into — to be kept alive over the call —, the output
    capacity the call needs)."""
    arena = np.ascontiguousarray(arena)
    timestamps = np.ascontiguousarray(timestamps, dtype=np.int64)
    field_offs = np.ascontiguousarray(field_offs, dtype=np.int32)
    field_lens = np.ascontiguousarray(field_lens, dtype=np.int32)
    frags_blob, frag_lens = _key_struct(key_frags)
    F = len(key_frags)
    n = len(timestamps)
    sf, si = (1, F) if event_major else (n, 1)
    prefix_b = np.frombuffer(prefix or b"\0", dtype=np.uint8)
    ts_b = np.frombuffer(ts_frag or b"\0", dtype=np.uint8)
    suffix_b = np.frombuffer(suffix or b"\0", dtype=np.uint8)
    # worst case: every value byte expands 6x (\u00XX), plus per-row
    # framing — mirrors the C row bound so -1 can only mean "unsupported"
    cap = int(n * (len(prefix) + len(ts_frag) + 48 + int(frag_lens.sum())
                   + 4 * F + len(suffix) + 2) + 6 * len(arena) + 64)
    alive = (arena, timestamps, field_offs, field_lens, frags_blob,
             frag_lens, prefix_b, ts_b, suffix_b)
    return (_u8(arena), len(arena), _i64(timestamps), n, F,
            _u8(frags_blob), _i32(frag_lens), _i32(field_offs),
            _i32(field_lens), sf, si,
            _u8(prefix_b), len(prefix), 1 if prefix_members else 0,
            _u8(ts_b), len(ts_frag), ts_mode, 1 if ts_first else 0,
            _u8(suffix_b), len(suffix)), alive, cap


def ndjson_serialize(arena: np.ndarray, timestamps: np.ndarray,
                     key_frags: tuple, field_offs: np.ndarray,
                     field_lens: np.ndarray, prefix: bytes,
                     prefix_members: bool, ts_frag: bytes, ts_mode: int,
                     ts_first: bool, suffix: bytes = b"\n",
                     event_major: bool = False) -> Optional[memoryview]:
    """NDJSON rows from columnar spans (loongshard zero-copy fast path).

    key_frags: per-field ``b'"key": "'`` fragments (keys pre-escaped by the
    caller); prefix: row head (``{`` + encoded group tags, no trailing
    separator); ts_frag: ``b'"<key>": '``.  Caller guarantees every emitted
    span is valid UTF-8 (json.dumps replacement semantics live on the
    Python fallback).  Returns a memoryview over the output buffer, or
    None when the library is unavailable / the row shape is unsupported."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lct_ndjson_serialize") \
            or len(key_frags) > 64:
        return None
    args, _alive, cap = _ndjson_args(
        arena, timestamps, key_frags, field_offs, field_lens, prefix,
        prefix_members, ts_frag, ts_mode, ts_first, suffix, event_major)
    out = np.empty(cap, dtype=np.uint8)
    written = lib.lct_ndjson_serialize(*args, _u8(out), cap)
    if written < 0:
        return None
    return memoryview(out)[:written]


def ndjson_serialize_append(path: str, scratch: Optional[np.ndarray],
                            arena: np.ndarray, timestamps: np.ndarray,
                            key_frags: tuple, field_offs: np.ndarray,
                            field_lens: np.ndarray, prefix: bytes,
                            prefix_members: bool, ts_frag: bytes,
                            ts_mode: int, ts_first: bool,
                            suffix: bytes = b"\n"
                            ) -> Tuple[Optional[Tuple[int, float, float]],
                                       Optional[np.ndarray]]:
    """`ndjson_serialize` and `append_file` of its rows in ONE native call
    (the file sink's flush of a one-group batch: the sender thread lets go
    of the interpreter lock once, not once for the assembly and three
    times for the write).  The native side first ORs the arena's bytes and
    declines an arena that holds one >= 0x80 — the caller then takes the
    path that checks span by span.  ``scratch`` is the caller's own output
    buffer from its last call (None at first); the one to keep comes back.

    Returns ((bytes appended, seconds assembling, seconds writing),
    scratch); (None, scratch) when declined or unavailable; raises OSError
    when the write failed."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lct_ndjson_serialize_append") \
            or len(key_frags) > 64:
        return None, scratch
    args, _alive, cap = _ndjson_args(
        arena, timestamps, key_frags, field_offs, field_lens, prefix,
        prefix_members, ts_frag, ts_mode, ts_first, suffix, False)
    if scratch is None or len(scratch) < cap:
        scratch = np.empty(cap, dtype=np.uint8)
    phase_ns = np.zeros(2, dtype=np.int64)
    rc = lib.lct_ndjson_serialize_append(
        os.fsencode(path), *args, _u8(scratch), len(scratch),
        _i64(phase_ns))
    if rc <= -1000:
        raise OSError(-rc - 1000, os.strerror(-rc - 1000), path)
    if rc < 0:
        return None, scratch
    return (int(rc), phase_ns[0] / 1e9, phase_ns[1] / 1e9), scratch


def append_file(path: str, data) -> bool:
    """Append ``data`` (bytes or a memoryview) to the file at ``path``,
    created if missing: open, write all of it, close — in one native call,
    so the interpreter lock is let go of once for the three system calls
    and not three times (the file sink's write, flusher/file.py).  False
    when the library is unavailable (the caller writes through Python);
    raises OSError when a call failed."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lct_append_file"):
        return False
    buf = np.frombuffer(data, dtype=np.uint8)
    rc = lib.lct_append_file(os.fsencode(path), _u8(buf), len(buf))
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc), path)
    return True


def _codec(fn_c, fn_bound, data: bytes) -> Optional[bytes]:
    lib = get_lib()
    if lib is None or not hasattr(lib, fn_c):
        return None
    src = np.frombuffer(data, dtype=np.uint8)
    cap = int(getattr(lib, fn_bound)(len(src)))
    out = np.empty(max(cap, 16), dtype=np.uint8)
    n = getattr(lib, fn_c)(_u8(src), len(src), _u8(out), len(out))
    if n < 0:
        return None
    return out[:n].tobytes()


def lz4_compress(data: bytes) -> Optional[bytes]:
    """LZ4 block format (raw, no frame) — SLS's default wire codec."""
    return _codec("lct_lz4_compress", "lct_lz4_bound", data)


def lz4_decompress(data: bytes, raw_size: int) -> Optional[bytes]:
    lib = get_lib()
    if lib is None or not hasattr(lib, "lct_lz4_decompress"):
        return None
    src = np.frombuffer(data, dtype=np.uint8)
    out = np.empty(max(raw_size, 1), dtype=np.uint8)
    n = lib.lct_lz4_decompress(_u8(src), len(src), _u8(out), raw_size)
    if n < 0:
        return None
    return out[:n].tobytes()


def snappy_compress(data: bytes) -> Optional[bytes]:
    """Snappy block format — required by Prometheus remote-write."""
    return _codec("lct_snappy_compress", "lct_snappy_bound", data)


def snappy_decompress(data: bytes) -> Optional[bytes]:
    lib = get_lib()
    if lib is None or not hasattr(lib, "lct_snappy_decompress"):
        return None
    src = np.frombuffer(data, dtype=np.uint8)
    raw = lib.lct_snappy_uncompressed_len(_u8(src), len(src))
    if raw < 0:
        return None
    out = np.empty(max(int(raw), 1), dtype=np.uint8)
    n = lib.lct_snappy_decompress(_u8(src), len(src), _u8(out), int(raw))
    if n != raw:
        return None
    return out[:n].tobytes()
