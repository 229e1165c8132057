"""Packed dispatch I/O (ops/packed_io.py): one buffer in, one buffer out.

  * the packed entry of every kernel the cells run — ``ExtractKernel``,
    ``PallasExtractKernel`` (interpreted here), ``MatchKernel``, the
    ``extract → keep`` and ``json_fields → keep`` programs — against its
    tuple entry, array for array, at two geometries, one of them 256 × 2048
    where the lengths fill half a row and the tail rounds up;
  * a ring slot's ``rows`` / ``lengths`` are views over its one ``packed``
    array, and a repack after ``release()`` leaves no stale length;
  * the window takes the packed entry from a callable that offers one and
    ``(rows, lengths)`` from one that does not (an override, a lane-placed
    kernel, a gated callable, a program with a ``struct_index`` stage), and
    the plane counts the arrays that cross either way;
  * a chunk submitted packed whose materialisation faults is recovered on
    the bare kernel's tuple entry and delivers the same rows;
  * the Pallas call inside the packed module keeps the operand and result
    shapes the benchmark's roofline reader takes from the operation's text.
"""

import os
import re
import sys

import numpy as np
import pytest

from loongcollector_tpu import chaos, models
from loongcollector_tpu.chaos import ChaosPlan, FaultSpec
from loongcollector_tpu.monitor.alarms import AlarmManager
from loongcollector_tpu.ops import chip_lanes
from loongcollector_tpu.ops import device_stream as ds
from loongcollector_tpu.ops import fused_pipeline as fp
from loongcollector_tpu.ops import packed_io
from loongcollector_tpu.ops.chip_lanes import ChipLaneFault, lane_gated
from loongcollector_tpu.ops.device_plane import (DevicePlane,
                                                 LatencyInjectedKernel)
from loongcollector_tpu.ops.kernels.field_extract import (ExtractKernel,
                                                          MatchKernel)
from loongcollector_tpu.ops.kernels.field_extract_pallas import \
    PallasExtractKernel
from loongcollector_tpu.ops.regex.engine import (RegexEngine,
                                                 _LanePlacedKernel)
from loongcollector_tpu.ops.regex.program import compile_tier1

from test_fused_pipeline import build_pipeline
from test_json_fields import JSON_FILTER

APACHE = (r'(\S+) (\S+) (\S+) \[([^\]]+)\] "(\S+) (\S+) ([^"]*)" '
          r'(\d{3}) (\d+)')
REGEX_FILTER = {
    "inputs": [], "flushers": [{"Type": "flusher_stdout"}],
    "processors": [
        {"Type": "processor_parse_regex_tpu", "Regex": APACHE,
         "Keys": ["ip", "ident", "user", "time", "method", "url",
                  "protocol", "status", "size"]},
        {"Type": "processor_filter_native",
         "Include": {"status": r"[45]\d\d"}}]}

#: 256 x 2048 is the multiline cell's slot: 4·B / L is half a row
GEOMETRIES = [(64, 256), (256, 2048)]


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.setenv("LOONG_FUSED", "1")
    monkeypatch.setenv("LOONG_NATIVE_T1", "0")
    # the single-device path, as the benchmark's cells run it (conftest's
    # eight virtual devices would make every unbound parse a sharded one)
    monkeypatch.setenv("LOONG_SHARDED", "0")
    prev = models.set_columnar_enabled(True)
    chaos.reset()
    DevicePlane.reset_for_testing()
    ds.reset_for_testing()
    fp.reset_for_testing()
    yield
    chaos.reset()
    models.set_columnar_enabled(prev)
    # the geometries compiled here read as a recompile storm: leave no
    # alarm in the singleton for the next file on this worker
    AlarmManager.instance().flush()
    chip_lanes.set_thread_lane(None)
    chip_lanes.reset_for_testing()
    DevicePlane.reset_for_testing()
    ds.reset_for_testing()
    fp.reset_for_testing()


def _apache_lines(n, L):
    lines = []
    for i in range(n):
        pad = "x" * (i * 7 % (L - 130))
        status = (200, 404, 500, 302)[i % 4]
        lines.append(f'10.0.{i % 256}.9 - u{i} [02/Oct/2026:10:00:{i % 60:02d}'
                     f' +0000] "GET /p/{i:06d}/{pad} HTTP/1.1" {status} {i}'
                     .encode())
    lines[n // 2] = b"not an access line"
    return lines


def _json_lines(n, L):
    lines = []
    for i in range(n):
        level = ("ERROR", "INFO", "WARN", "DEBUG")[i % 4]
        msg = "m" * (i * 5 % (L - 80))
        lines.append(f'{{"level": "{level}", "n": {i}, "msg": "{msg}"}}'
                     .encode())
    lines[n // 3] = b"not json"
    lines[n // 3 + 1] = b'{"level": "a\\nb", "n": 1}'
    return lines


def _spans(lines):
    lens = np.array([len(x) for x in lines], np.int32)
    arena = np.frombuffer(b"".join(lines), np.uint8)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int64)
    return arena, offs, lens


def _packed_slot(lines, B, L):
    slot = ds.batch_ring().lease(B, L)
    return slot, slot.pack(*_spans(lines))


# -- the layout -----------------------------------------------------------------

@pytest.mark.parametrize("B,L,R", [
    (256, 128, 264), (1024, 512, 1032), (8192, 256, 8320), (256, 2048, 257),
    (32, 4096, 33), (65536, 128, 67584), (512, 1024, 514)])
def test_packed_rows_and_their_inverse(B, L, R):
    assert packed_io.packed_rows(B, L) == R
    assert packed_io.batch_rows(R, L) == B
    # a row count no batch packs into is refused, never rounded
    if packed_io.packed_rows(B - 1, L) != R - 1:
        with pytest.raises(ValueError):
            packed_io.batch_rows(R - 1, L)


@pytest.mark.parametrize("B,L", [(1024, 512), (256, 2048)])
def test_slot_views_alias_the_one_buffer_and_a_repack_leaves_no_length(B, L):
    ring = ds.batch_ring()
    first = _apache_lines(B - 3, L)
    slot, batch = _packed_slot(first, B, L)
    assert slot.packed.shape == (packed_io.packed_rows(B, L), L)
    assert slot.packed.flags.c_contiguous
    assert batch.rows is slot.rows and batch.lengths is slot.lengths
    assert np.shares_memory(slot.rows, slot.packed)
    assert np.shares_memory(slot.lengths, slot.packed)
    assert not np.shares_memory(slot.origins, slot.packed)
    # the tail IS the lengths, little-endian, from the first byte after
    # the rows; what is left of its last row stays zero
    tail = slot.packed[B:].reshape(-1)
    assert tail[:4 * B].tobytes() == slot.lengths.astype("<i4").tobytes()
    assert not tail[4 * B:].any()
    np.testing.assert_array_equal(slot.lengths[:B - 3],
                                  [len(x) for x in first])
    # what the byte accounting sees is what it saw: rows, lengths, origins
    assert slot.nbytes() == B * L + 4 * B + 4 * B
    slot.release()
    again = ring.lease(B, L)
    assert again is slot                        # the pooled slot, reused
    again.pack(*_spans(first[:5]))
    assert not again.lengths[5:].any(), "a stale length crossed the repack"
    assert not again.packed[B:].reshape(-1)[20:].any()
    assert not again.rows[5:].any()
    again.release()


# -- packed entry against tuple entry ---------------------------------------------

def _kernel(name):
    if name == "extract_xla":
        return ExtractKernel(compile_tier1(APACHE)), _apache_lines
    if name == "extract_pallas":
        return (PallasExtractKernel(compile_tier1(APACHE), interpret=True),
                _apache_lines)
    if name == "line_classify":
        return MatchKernel(compile_tier1(APACHE)), _apache_lines
    if name == "extract_keep":
        p = build_pipeline(REGEX_FILTER, "packed-regex-filter")
        return p._fused_runs[0].program(), _apache_lines
    p = build_pipeline(JSON_FILTER, "packed-json-filter")
    return p._fused_runs[0].program(), _json_lines


#: outputs a program hands over per dispatch on its tuple entry
N_OUTPUTS = {"extract_xla": 3, "extract_pallas": 3, "line_classify": 1,
             "extract_keep": 4, "json_keep": 7}


@pytest.mark.parametrize("B,L", GEOMETRIES, ids=lambda g: str(g))
@pytest.mark.parametrize("name", list(N_OUTPUTS))
def test_packed_entry_equals_tuple_entry(name, B, L):
    kern, make = _kernel(name)
    slot, batch = _packed_slot(make(B - 7, L), B, L)
    want = [np.asarray(a) for a in kern(batch.rows, batch.lengths)]
    packed = np.asarray(kern.packed_call(slot.packed))
    assert packed.dtype == np.int32 and packed.ndim == 2
    assert packed.shape[0] == B
    got = kern.unpack(packed)
    assert len(want) == len(got) == N_OUTPUTS[name]
    for i, (a, b) in enumerate(zip(want, got)):
        assert a.dtype == b.dtype and a.shape == b.shape, (i, a.dtype,
                                                            b.dtype)
        np.testing.assert_array_equal(a, b, err_msg=f"output {i}")
        if b.dtype != bool:
            assert np.shares_memory(b, packed), "a split that copies"
    # not vacuous: rows that match and rows that do not
    assert 0 < int(np.asarray(want[0]).astype(bool)[:B - 7].sum()) < B - 7
    slot.release()


def test_a_program_with_a_struct_index_stage_offers_no_packed_entry():
    """Its outputs are bitmaps as wide as the row, no per-row column."""
    spec = fp.StageSpec("struct_index", ("json", 0), ("struct", "json"))
    program = fp.FusedProgramKernel([spec], "no-packed-entry")
    assert program.packed_call is None and program.unpack is None
    program.geometries = {(32, 128)}
    assert program.warm() == 1                  # warms the tuple entry


def test_a_shape_the_columns_do_not_declare_fails_the_trace():
    import jax.numpy as jnp
    cols = packed_io.Columns(packed_io.span_columns(2))
    ok = jnp.zeros(8, bool)
    with pytest.raises(TypeError, match="declared"):
        cols.pack((ok, jnp.zeros((8, 3), jnp.int32),
                   jnp.zeros((8, 2), jnp.int32)))
    with pytest.raises(TypeError, match="32-bit"):
        cols.pack((ok, jnp.zeros((8, 2), jnp.float32),
                   jnp.zeros((8, 2), jnp.int32)))
    with pytest.raises(ValueError, match="packed output"):
        cols.unpack(np.zeros((8, 4), np.int32))


# -- what the window takes, and what the plane counts -------------------------------

class _Lane:
    """As much of a chip lane as ``_LanePlacedKernel`` reads."""
    index = 0

    def __init__(self):
        import jax
        self.device = jax.devices()[0]


def _callable(case, kern):
    if case == "bare_kernel":
        return kern
    if case == "kernel_override":
        return LatencyInjectedKernel(kern, 0.0, serialize=False)
    if case == "lane_placed":
        return _LanePlacedKernel(kern, _Lane())
    if case == "gated":
        return lane_gated(chip_lanes.reset_for_testing().lane_for_worker(0),
                          kern)
    return lambda rows, lengths: kern(rows, lengths)      # "tuple_entry"


@pytest.mark.parametrize("case,h2d,d2h", [
    ("bare_kernel", 1, 1),
    # the fake's outputs are no device arrays: no copy to start
    ("kernel_override", 2, 0),
    ("lane_placed", 2, 3),
    ("gated", 2, 3),
    ("tuple_entry", 2, 3)])
def test_window_takes_the_entry_the_callable_offers(case, h2d, d2h):
    plane = DevicePlane.reset_for_testing()
    kern = ExtractKernel(compile_tier1(r"(\w+) (\d+)w"))
    call = _callable(case, kern)
    assert (getattr(call, "packed_call", None) is not None) \
        == (case == "bare_kernel")
    seen = []
    window = plane.open_stream(
        depth=2, deliver=lambda c, outs: seen.append((c, outs)))
    lines = [b"abc 123w", b"nope", b"de 4w"]
    for _ in range(3):
        window.submit_rows(call, *_spans(lines), kernel=kern)
    window.drain()
    u = plane.utilization()
    assert u["dispatched_total"] == 3
    assert (u["h2d_arrays_total"], u["d2h_arrays_total"]) \
        == (3 * h2d, 3 * d2h)
    assert u["d2h_prefetched_total"] == (3 if d2h else 0)
    for c, (ok, off, length) in seen:
        # whichever entry it rode, deliver gets the kernel's tuple
        assert (c.unpack is not None) == (case == "bare_kernel")
        assert c.kernel is kern
        np.testing.assert_array_equal(np.asarray(ok)[:3], [True, False, True])
        np.testing.assert_array_equal(np.asarray(length)[2], [2, 1])
        np.testing.assert_array_equal(np.asarray(off)[2], [0, 3])
    assert plane.inflight_bytes() == 0
    assert ds.batch_ring().leased_total() == 0


def test_engine_and_fused_owner_ride_the_packed_entry(monkeypatch):
    """An unbound parse and an unbound fused dispatch cross twice a
    dispatch; under a kernel override, or sharded over the mesh, a parse
    keeps the tuple entry."""
    plane = DevicePlane.reset_for_testing()
    arena, offs, lens = _spans(_apache_lines(300, 256))
    eng = RegexEngine(APACHE)
    want = eng.parse_batch_async(arena, offs, lens).result()
    u = plane.utilization()
    assert u["dispatched_total"] >= 1
    assert u["h2d_arrays_total"] == u["d2h_arrays_total"] \
        == u["dispatched_total"]
    program = build_pipeline(REGEX_FILTER, "packed-owner") \
        ._fused_runs[0].program()
    res = fp.FusedDispatch(program, arena, offs, lens).dispatch().result()
    u2 = plane.utilization()
    n = u2["dispatched_total"] - u["dispatched_total"]
    assert n >= 1 and program.dispatch_count == n
    assert u2["h2d_arrays_total"] - u["h2d_arrays_total"] == n
    assert u2["d2h_arrays_total"] - u["d2h_arrays_total"] == n
    np.testing.assert_array_equal(res.stages[0][0], want.ok)
    np.testing.assert_array_equal(res.stages[0][1], want.cap_off)
    np.testing.assert_array_equal(res.stages[0][2], want.cap_len)
    # the override is handed (rows, lengths), as it always was
    eng.set_device_kernel_override(
        LatencyInjectedKernel(eng._segment_kernel, 0.0, serialize=False))
    again = eng.parse_batch_async(arena, offs, lens).result()
    u3 = plane.utilization()
    n = u3["dispatched_total"] - u2["dispatched_total"]
    assert u3["h2d_arrays_total"] - u2["h2d_arrays_total"] == 2 * n
    np.testing.assert_array_equal(again.ok, want.ok)
    np.testing.assert_array_equal(again.cap_off, want.cap_off)
    # the sharded kernel places its own shards
    monkeypatch.setenv("LOONG_SHARDED", "1")
    sharded = RegexEngine(APACHE).parse_batch_async(arena, offs, lens).result()
    u4 = plane.utilization()
    n = u4["dispatched_total"] - u3["dispatched_total"]
    assert n >= 1
    assert u4["h2d_arrays_total"] - u3["h2d_arrays_total"] == 2 * n
    np.testing.assert_array_equal(sharded.ok, want.ok)
    np.testing.assert_array_equal(sharded.cap_len, want.cap_len)


# -- a packed chunk that faults ------------------------------------------------------

class _FaultsOnce:
    """A kernel whose packed entry raises a chip-lane fault the first
    time: the dispatch errors, the recovery must not need it."""

    def __init__(self, kern):
        self.kern, self.unpack, self.faults = kern, kern.unpack, 0

    def __call__(self, rows, lengths):
        return self.kern(rows, lengths)

    def packed_call(self, packed):
        if not self.faults:
            self.faults += 1
            raise ChipLaneFault("injected at device_plane.chip_lane.0")
        return self.kern.packed_call(packed)


@pytest.mark.parametrize("fault", ["device_plane.h2d", "device_plane.submit",
                                   "device_plane.ring_advance",
                                   "chip_lane_fault"])
def test_a_faulted_packed_chunk_is_recovered_on_the_bare_kernel(
        fault, monkeypatch):
    from loongcollector_tpu.ops.regex import engine as engine_mod
    monkeypatch.setattr(engine_mod, "MAX_BATCH", 256)
    plane = DevicePlane.reset_for_testing()
    arena, offs, lens = _spans(_apache_lines(700, 256))
    want = RegexEngine(APACHE).parse_batch_async(arena, offs, lens).result()
    assert 0 < want.ok.sum() < 700

    eng = RegexEngine(APACHE)
    kern = eng._segment_kernel
    tuple_calls = []
    tuple_fn = kern._fn
    monkeypatch.setattr(kern, "_fn", lambda rows, lengths: (
        tuple_calls.append(rows.shape), tuple_fn(rows, lengths))[1])
    before = plane.utilization()
    if fault == "chip_lane_fault":
        eng.set_device_kernel_override(_FaultsOnce(kern))
    else:
        chaos.install(ChaosPlan(11, {fault: FaultSpec(
            prob=1.0, kinds=(chaos.ACTION_ERROR,), after_hits=1,
            max_faults=1)}))
    try:
        got = eng.parse_batch_async(arena, offs, lens, depth=3).result()
    finally:
        chaos.uninstall()
    np.testing.assert_array_equal(got.ok, want.ok)
    np.testing.assert_array_equal(got.cap_off, want.cap_off)
    np.testing.assert_array_equal(got.cap_len, want.cap_len)
    after = plane.utilization()
    assert after["dispatched_total"] - before["dispatched_total"] == 3
    # every chunk was submitted packed; the faulted one was re-run on the
    # bare kernel's (rows, lengths) entry (a lane fault parses on the host)
    assert tuple_calls == ([] if fault == "chip_lane_fault"
                           else [(256, 256)])
    assert after["h2d_arrays_total"] - before["h2d_arrays_total"] \
        == after["d2h_arrays_total"] - before["d2h_arrays_total"] \
        == (3 if fault == "device_plane.ring_advance" else 2)
    assert plane.inflight_bytes() == 0
    assert ds.batch_ring().leased_total() == 0


# -- the shapes the benchmark reads --------------------------------------------------

@pytest.mark.parametrize("B,L", [(1024, 512), (256, 2048)])
@pytest.mark.parametrize("entry", ["tuple", "packed"])
def test_pallas_call_keeps_its_shapes_in_the_lowered_text(entry, B, L):
    """perfbench/benchlib/roofline.py ``extract_shapes`` takes (rows, width,
    captures) from the ``extract`` operation's text: the u8[B,L] operand and
    the widest s32[B,C] result.  The packed module's slice, bitcast and
    concatenate sit around that call, not in it."""
    import jax
    import jax.numpy as jnp
    sys.path.append(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench"))
    from benchlib import roofline
    kern = PallasExtractKernel(compile_tier1(APACHE))
    if entry == "tuple":
        fn, args = kern._fn, (jax.ShapeDtypeStruct((B, L), jnp.uint8),
                              jax.ShapeDtypeStruct((B,), jnp.int32))
    else:
        fn, args = kern.packed_call, (jax.ShapeDtypeStruct(
            (packed_io.packed_rows(B, L), L), jnp.uint8),)
    # lowered for the chip's platform: no device, nothing compiles or runs
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)) \
        .compiler_ir(dialect="hlo").as_hlo_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1
    call = re.sub(r"backend_config=.*", "", calls[0])
    assert f"u8[{B},{L}]" in call and f"s32[{B},9]" in call
    assert roofline.extract_shapes(call) == (B, L, 9)
    assert roofline.extract_bytes(B, L, 9) == B * L + 4 * B + 72 * B
    root = [ln for ln in text.splitlines() if "ROOT" in ln][-1]
    if entry == "packed":
        assert f"s32[{B},19]" in root and "concatenate" in root
        assert "bitcast-convert" in text
    else:
        assert "concatenate" not in root
