"""processor_grok's list program (PR 35): a ``Match`` list whose members are
all on the SEGMENT tier is ONE device program a group — every member's
extract and the first-match choice in one module, one dispatch, one pair of
matrices back, no host classify and no per-member subsets.

Held here, on the CPU (the XLA path, and the Pallas path interpreted): the
program alone against ``re.fullmatch`` member by member over every kind of
line the cell's source makes; the processor on the forced device route
against the plain reference on hand-made rows, against ``process()`` and
against the per-member path record for record; groups in flight in source
order through the worker's lane ring; an injected window fault re-running one
chunk on the same program; a chip-lane fault, a sick lane and a real failure
of the program landing on the per-member path with the counters saying so;
the routing rule applied to the whole group's byte sum; and the ``grok``
section's counters adding up over both paths.
"""

import numpy as np
import pytest

from loongcollector_tpu import chaos, models, trace
from loongcollector_tpu.chaos import ChaosPlan, FaultSpec
from loongcollector_tpu.ops import chip_lanes
from loongcollector_tpu.ops.device_batch import LENGTH_BUCKETS
from loongcollector_tpu.ops.device_plane import (DevicePlane,
                                                 set_budget_relief)
from loongcollector_tpu.ops.kernels.match_list import MatchListKernel
from loongcollector_tpu.ops.packed_io import packed_rows
from loongcollector_tpu.ops.regex import engine as engine_mod
from loongcollector_tpu.ops.regex.engine import PendingMatchList
from loongcollector_tpu.pipeline.pipeline import CollectionPipeline
from loongcollector_tpu.pipeline.queue.process_queue_manager import \
    ProcessQueueManager
from loongcollector_tpu.processor import grok as grok_mod
from loongcollector_tpu.runner.processor_runner import (ProcessorRunner,
                                                        WorkerLane)
from test_processor_grok_window import (ECHO, MATCH, _device_route,  # noqa: F401
                                        _expected, _fresh_planes, _group,
                                        _lines, _Mgr, _per_member,
                                        _processor, _records, _reference,
                                        _sink_records)

MEMBER1 = (b'10.1.2.3 - alice [04/Oct/2026:10:00:00 +0000] '
           b'"GET /api/v1/resource/000000000007?q=1 HTTP/1.1" 200 512 '
           b'"http://ref.example/" "curl/8.0" 0.004 0.003')


def _status(name="grok-window"):
    return grok_mod.status()[name]


def _routing():
    return engine_mod.routing_status()


# -- the program alone ----------------------------------------------------------------

@pytest.mark.parametrize("path", ["xla", "interpret"])
def test_the_list_program_equals_re_member_by_member(path):
    """Member index and every span, for every kind of line of the cell's
    source, through the tuple entry and through the packed entry."""
    src, lines = _lines(43, 256)
    lines = [ln[:-1] for ln in lines]
    p = _processor()
    kern = MatchListKernel(
        [e._segment_kernel.program for e, _ in p._engines], p._placement,
        len(p._keys), pallas=path == "interpret", interpret=True)
    B, L = 256, 256
    rows = np.zeros((B, L), np.uint8)
    lens = np.zeros(B, np.int32)
    for r, ln in enumerate(lines):
        rows[r, :len(ln)] = np.frombuffer(ln, np.uint8)
        lens[r] = len(ln)
    member, off, length = (np.asarray(a) for a in kern(rows, lens))
    packed = np.zeros((packed_rows(B, L), L), np.uint8)
    packed[:B] = rows
    packed[B:].reshape(-1)[:4 * B] = lens.astype("<i4").view(np.uint8)
    again = kern.unpack(np.asarray(kern.packed_call(packed)))
    for a, b in zip((member, off, length), again):
        assert np.array_equal(a, b)
    assert member.dtype == np.int32 and off.shape == length.shape == (B, 14)
    rxs = [e._re for e, _ in p._engines]
    seen = set()
    for r, ln in enumerate(lines):
        want_member, want = -1, {}
        for i, rx in enumerate(rxs):
            m = rx.fullmatch(ln)
            if m is not None:
                caps, columns = p._placement[i]
                want_member = i
                want = {c: m.span(g + 1) for g, c in zip(caps, columns)
                        if m.span(g + 1)[0] >= 0}
                break
        got = {c: (int(off[r, c]), int(off[r, c] + length[r, c]))
               for c in range(14) if length[r, c] >= 0}
        assert int(member[r]) == want_member, (r, ln)
        assert got == want, (r, ln)
        seen.add(want_member)
    assert seen == {-1, 0, 1, 2, 3}
    assert {k["kind"] for k in src.kinds} == {
        "member1", "member2", "member3", "member4", "unmatched"}


def test_the_list_program_compiles_for_a_v5e_at_the_cells_geometry():
    """The four Mosaic kernels and the choice as one module, lowered by the
    chip's compiler for a described (not attached) v5e at 1024 x 512 through
    the packed entry: what Mosaic refuses there it refuses here."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    p = _processor()
    kern = MatchListKernel(
        [e._segment_kernel.program for e, _ in p._engines], p._placement,
        len(p._keys), pallas=True)
    x = jax.ShapeDtypeStruct((packed_rows(1024, 512), 512), jnp.uint8,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = kern.packed_call._fn.lower(x).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
    assert compiled.as_text().count("tpu_custom_call") >= 4
    assert "loong_grok_match_list" in compiled.as_text()


# -- hand-made rows through the processor ------------------------------------------------

def _hand_made(case):
    overlap = MEMBER1
    no_version = overlap.replace(b" HTTP/1.1", b"")
    no_bytes = overlap.replace(b" 200 512 ", b" 304 - ")
    nobody = b"no member of the list takes this line ("
    long_row = overlap.replace(b"?q=1", b"?q=" + b"x" * 5000)
    return {"overlap": [overlap],
            "optional_absent": [no_version, no_bytes, overlap],
            "no_member": [nobody, overlap, nobody],
            "overlong": [overlap, long_row, nobody, overlap],
            "one_row": [no_bytes]}[case]


@pytest.mark.parametrize("case", ["overlap", "optional_absent", "no_member",
                                  "overlong", "one_row"])
def test_hand_made_rows_on_the_list_program(monkeypatch, case):
    _device_route(monkeypatch)
    lines = [ln + b"\n" for ln in _hand_made(case)]
    want = _expected(_reference(), lines)
    p = _processor()
    g = _group(b"".join(lines))
    token = p.process_dispatch(g)
    assert isinstance(token[1], PendingMatchList)
    p.process_complete(g, token)
    assert _records(g) == want
    doc = _status()
    n_long = sum(len(ln) - 1 > LENGTH_BUCKETS[-1] for ln in lines)
    assert doc["list_program_rows_total"] == len(lines) - n_long
    assert doc["re_rows_total"] == n_long == (case == "overlong")
    assert doc["walker_rows_total"] == 0 and doc["dispatches_total"] == 1
    assert DevicePlane.instance().inflight_bytes() == 0
    keys = [[k for k, _ in rec] for rec in want]
    if case == "overlap":
        # member 2 alone would take the line too: the lowest member decides
        assert _reference().members[1].fullmatch(lines[0][:-1])
        assert keys[0][-1] == "upstream_response_time"
        assert doc["member_rows_total"] == [1, 0, 0, 0]
    if case == "optional_absent":
        assert "httpversion" not in keys[0] and "bytes" not in keys[1]
        assert "httpversion" in keys[2] and "bytes" in keys[2]
    if case == "no_member":
        assert keys[0] == keys[2] == ["rawLog"]
        assert doc["unmatched_rows_total"] == 2
        assert g.columns.parse_ok.tolist() == [False, True, False]
    if case == "overlong":
        assert keys[1][-1] == "upstream_response_time" and keys[2] == ["rawLog"]
        assert doc["device_rows_total"] == 2
        assert doc["member_rows_total"] == [3, 0, 0, 0]


def test_an_empty_group_and_a_group_of_absent_rows(monkeypatch):
    _device_route(monkeypatch)
    p = _processor(name="grok-empty")
    empty = models.PipelineEventGroup(models.SourceBuffer(64))
    empty.set_columns(models.ColumnarLogs(np.zeros(0, np.int32),
                                          np.zeros(0, np.int32)))
    assert p.process_dispatch(empty) is None
    assert "grok-empty" not in grok_mod.status()
    # every row lacks the source field: nothing to pack, nothing in flight
    absent = _group(b"x\ny\n")
    absent.columns.set_field("content", absent.columns.offsets,
                             np.full(2, -1, np.int32))
    assert p.process_dispatch(absent) is None
    assert DevicePlane.instance().inflight_bytes() == 0
    doc = _status("grok-empty")
    assert doc["rows_total"] == 2 and doc["unmatched_rows_total"] == 2
    assert doc["list_program_rows_total"] == 0
    assert not absent.columns.parse_ok.any()
    # an empty row is a row: it rides, and no member takes it
    blank = _group(b"\n" + MEMBER1 + b"\n")
    p.process(blank)
    assert _records(blank) == _expected(_reference(), [b"\n", MEMBER1 + b"\n"])
    assert _status("grok-empty")["list_program_rows_total"] == 2


# -- list program == process() == the per-member path -----------------------------------

@pytest.mark.parametrize("seed,max_batch", [(5, None), (2147483659, None),
                                            (71, 128)])
def test_dispatch_complete_equals_process_equals_the_per_member_path(
        monkeypatch, seed, max_batch):
    """``max_batch`` 128: the group rides as three chunks (slices of the
    rows), so the owner fills buffers instead of keeping the one chunk's
    copy back."""
    _device_route(monkeypatch)
    if max_batch:
        monkeypatch.setattr(engine_mod, "MAX_BATCH", max_batch)
    _src, lines = _lines(seed, 300)
    data = b"".join(lines)
    want = _expected(_reference(), lines)
    p = _processor()
    plane = DevicePlane.instance()
    g1 = _group(data)
    before = plane.dispatched_total()
    token = p.process_dispatch(g1)
    assert isinstance(token[1], PendingMatchList)
    assert plane.dispatched_total() - before == (3 if max_batch else 1), \
        "one dispatch a group"
    assert plane.inflight_bytes() > 0 and g1.columns.parse_ok is None
    p.process_complete(g1, token)
    assert plane.inflight_bytes() == 0
    g2 = _group(data)
    p.process(g2)
    old = _per_member(_processor(name="grok-members"))
    g3 = _group(data)
    old.process(g3)
    got1, got2, got3 = _records(g1), _records(g2), _records(g3)
    for k, w in enumerate(want):
        assert got1[k] == w and got2[k] == w and got3[k] == w, (k, w)
    assert np.array_equal(g1.columns.parse_ok, g3.columns.parse_ok)
    assert g1.columns.content_consumed
    new, ref = _status(), _status("grok-members")
    assert new["list_program_rows_total"] == new["rows_total"] == 600
    assert ref["list_program_rows_total"] == 0 and ref["rows_total"] == 300
    for key in ("device_rows_total", "walker_rows_total", "re_rows_total",
                "unmatched_rows_total"):
        assert new[key] == 2 * ref[key], key
    assert new["member_rows_total"] == [2 * v
                                        for v in ref["member_rows_total"]]
    assert _routing()["kernel_fallbacks_total"] == 0


def test_no_classify_span_and_one_dispatch_leg_on_the_list_path(tmp_path,
                                                                monkeypatch):
    _device_route(monkeypatch)
    _src, lines = _lines(29, 200)
    p = CollectionPipeline()
    assert p.init("grok-list-spans", {
        "inputs": [{"Type": "input_file",
                    "FilePaths": [str(tmp_path / "access.log")]}],
        "processors": [{"Type": "processor_grok", "Match": MATCH}],
        "flushers": [{"Type": "flusher_file",
                      "FilePath": str(tmp_path / "grok.jsonl")}]})
    g = _group(b"".join(lines))
    tracer = trace.enable()
    try:
        p.process([g])
        spans = tracer.finished_spans()
    finally:
        trace.disable()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp)
    assert "grok.classify" not in by_name and "grok.re_rows" not in by_name
    stage = "processor.processor_grok"
    (leg,) = by_name["grok.members.dispatch"]
    assert leg.parent_id == by_name[stage + ".dispatch"][0].span_id
    (apply,) = by_name["grok.apply"]
    assert apply.parent_id == by_name[stage + ".complete"][0].span_id
    under = [sp.name for sp in spans if sp.parent_id == leg.span_id]
    assert sorted(n for n in under if n in ("device.pack", "device.submit")) \
        == ["device.pack", "device.submit"]
    assert _status("grok-list-spans")["list_program_rows_total"] == 200
    p.stop(True)


# -- groups in flight ----------------------------------------------------------------------

def test_groups_in_flight_complete_in_source_order(tmp_path, monkeypatch):
    _device_route(monkeypatch)
    _src, lines = _lines(13, 1000)
    sink = tmp_path / "grok.jsonl"
    p = CollectionPipeline()
    assert p.init("grok-list-ring", {
        "inputs": [{"Type": "input_file",
                    "FilePaths": [str(tmp_path / "access.log")]}],
        "processors": [{"Type": "processor_grok", "Match": MATCH}],
        "flushers": [{"Type": "flusher_file", "FilePath": str(sink)}]})
    runner = ProcessorRunner(ProcessQueueManager(), _Mgr(p), thread_count=1)
    lane = WorkerLane(0, depth=4)
    set_budget_relief(runner._make_relief(lane))
    plane = DevicePlane.instance()
    held = []
    for k in range(0, 1000, 125):
        runner._handle_run(1, [_group(b"".join(lines[k:k + 125]))], lane)
        held.append((lane.pending_count(), plane.inflight_bytes()))
    assert max(n for n, _ in held) >= 3, held
    assert all(b > 0 for _, b in held)
    runner._complete_lane(lane)
    assert lane.pending_count() == 0 and plane.inflight_bytes() == 0
    p.flush_batch()
    got = _sink_records(sink)
    want = _expected(_reference(), lines)
    assert len(got) == 1000
    for k, (a, w) in enumerate(zip(got, want)):
        assert a == w, (k, a, w)
    doc = _status("grok-list-ring")
    assert doc["rows_total"] == doc["list_program_rows_total"] == 1000
    assert doc["dispatches_total"] == 8 == plane.dispatched_total()
    util = plane.utilization()
    assert util["h2d_arrays_total"] == util["d2h_arrays_total"] == 8
    p.stop(True)


# -- faults ----------------------------------------------------------------------------------

@pytest.mark.parametrize("point", ["device_plane.ring_advance",
                                   "device_plane.h2d"])
def test_an_injected_window_fault_reruns_one_chunk_on_the_same_program(
        monkeypatch, point):
    _device_route(monkeypatch)
    _src, lines = _lines(47, 240)
    want = _expected(_reference(), lines)
    p = _processor()
    chaos.install(ChaosPlan(7, {point: FaultSpec(
        prob=1.0, kinds=(chaos.ACTION_ERROR,), after_hits=1, max_faults=1)}))
    try:
        groups = [_group(b"".join(lines[k:k + 80])) for k in (0, 80, 160)]
        tokens = [p.process_dispatch(g) for g in groups]
        for g, token in zip(groups, tokens):
            p.process_complete(g, token)
        injected = chaos.fault_counts().get(point, 0)
    finally:
        chaos.uninstall()
    assert injected == 1
    got = [rec for g in groups for rec in _records(g)]
    assert got == want
    doc = _status()
    assert doc["list_program_rows_total"] == doc["rows_total"] == 240
    assert doc["dispatches_total"] == 3 and p._list_ok
    assert _routing()["kernel_fallbacks_total"] == 0
    assert DevicePlane.instance().inflight_bytes() == 0


@pytest.fixture
def lane(monkeypatch):
    monkeypatch.setenv("LOONG_LANE_TRIP_THRESHOLD", "3")
    monkeypatch.setenv("LOONG_LANE_COOLDOWN_S", "30")
    lane = chip_lanes.reset_for_testing().lane_for_worker(0)
    chip_lanes.set_thread_lane(lane)
    yield lane
    chip_lanes.set_thread_lane(None)
    chip_lanes.reset_for_testing()


@pytest.mark.parametrize("how", ["fault", "open_lane"])
def test_a_sick_chip_lane_lands_on_the_per_member_path(monkeypatch, lane,
                                                       how):
    """An injected single-chip fault on the list dispatch, or a lane whose
    breaker is open: the group takes the per-member path (which respills on
    the host), nothing is lost, the processor keeps the list program."""
    _device_route(monkeypatch)
    _src, lines = _lines(53, 160)
    want = _expected(_reference(), lines)
    p = _processor()
    g = _group(b"".join(lines))
    if how == "fault":
        chaos.install(ChaosPlan(3, {lane.fault_point: FaultSpec(
            prob=1.0, kinds=(chaos.ACTION_ERROR,), max_faults=1)}))
    else:
        for _ in range(3):
            lane.breaker.on_failure()
        assert not lane.breaker.allow_probe()
    try:
        token = p.process_dispatch(g)
        p.process_complete(g, token)
    finally:
        chaos.uninstall()
    assert _records(g) == want
    doc = _status()
    assert doc["rows_total"] == 160 and doc["list_program_rows_total"] == 0
    assert doc["device_rows_total"] + doc["walker_rows_total"] \
        + doc["re_rows_total"] + doc["unmatched_rows_total"] == 160
    assert p._list_ok and _routing()["kernel_fallbacks_total"] == 0
    assert lane.respilled_events() > 0
    assert DevicePlane.instance().inflight_bytes() == 0
    assert lane.inflight_bytes() == 0
    if how == "fault":
        # the lane is still closed: the next group rides the list program,
        # placed on the lane's chip
        g2 = _group(b"".join(lines))
        p.process(g2)
        assert _records(g2) == want
        assert _status()["list_program_rows_total"] == 160


def test_a_real_failure_of_the_program_pins_the_per_member_path(monkeypatch):
    _device_route(monkeypatch)
    _src, lines = _lines(59, 200)
    want = _expected(_reference(), lines)
    p = _processor()
    kern = p._list_program(None)

    def mosaic_says_no(packed):
        raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")
    monkeypatch.setattr(kern, "packed_call", mosaic_says_no)
    before = _routing()["kernel_fallbacks_total"]
    g = _group(b"".join(lines))
    token = p.process_dispatch(g)
    assert isinstance(token[1], PendingMatchList)
    p.process_complete(g, token)
    assert _records(g) == want
    assert _routing()["kernel_fallbacks_total"] == before + 1
    assert not p._list_ok
    g2 = _group(b"".join(lines))
    token = p.process_dispatch(g2)
    assert not isinstance(token[1], PendingMatchList)
    p.process_complete(g2, token)
    assert _records(g2) == want
    assert _routing()["kernel_fallbacks_total"] == before + 1
    doc = _status()
    assert doc["rows_total"] == 400 and doc["list_program_rows_total"] == 0
    assert doc["dispatches_total"] == 2
    assert doc["device_rows_total"] + doc["unmatched_rows_total"] == 400
    assert DevicePlane.instance().inflight_bytes() == 0


# -- when it engages -------------------------------------------------------------------------

def test_the_routing_rule_reads_the_whole_groups_byte_sum(monkeypatch):
    """On an accelerator the crossover decides: a group whose byte sum is
    above it rides the list program even where member 1's own subset would
    have stayed under it; a group under it takes the per-member path, whose
    subsets run on the host walker."""
    monkeypatch.delenv("LOONG_NATIVE_T1", raising=False)
    monkeypatch.setattr(engine_mod, "_native_host_mode", lambda: False)
    monkeypatch.setattr(engine_mod, "_device_min_bytes_cached", 45_000)
    _src, lines = _lines(61, 250)
    ref = _reference()
    big, small = lines[:200], lines[200:]
    whole = sum(len(ln) - 1 for ln in big)
    first = sum(len(ln) - 1 for ln in big if ref.member_of(ln[:-1]) == 0)
    assert first < 45_000 < whole
    p = _processor()
    g = _group(b"".join(big))
    token = p.process_dispatch(g)
    assert isinstance(token[1], PendingMatchList)
    p.process_complete(g, token)
    assert _records(g) == _expected(ref, big)
    assert _status()["list_program_rows_total"] == 200
    g = _group(b"".join(small))
    assert p.process_dispatch(g) is None, "host subsets finish at dispatch"
    assert _records(g) == _expected(ref, small)
    doc = _status()
    assert doc["list_program_rows_total"] == 200 and doc["rows_total"] == 250
    assert doc["walker_rows_total"] > 0


def test_which_lists_the_program_serves(monkeypatch):
    assert _processor()._list_ok
    # a member no device tier holds, a list of one
    assert not _processor([MATCH[0], ECHO] + MATCH[1:], "grok-echo")._list_ok
    assert not _processor([MATCH[0]], "grok-one")._list_ok
    # an unbound dispatch that would shard over a mesh keeps the members'
    # own (sharded) dispatches
    monkeypatch.setenv("LOONG_SHARDED", "1")
    engine_mod.clear_engine_cache()
    meshed = _processor(name="grok-mesh")
    assert meshed._list_ok and meshed._list_program(None) is None
    # one kernel object a process and list
    monkeypatch.setenv("LOONG_SHARDED", "0")
    engine_mod.clear_engine_cache()
    a, b = _processor(name="grok-a"), _processor(name="grok-b")
    assert a._list_program(None) is b._list_program(None)
    assert a._list_program(None).family == "grok_match_list"


# -- the counters -----------------------------------------------------------------------------

def test_the_counters_add_up_over_both_paths(monkeypatch):
    _device_route(monkeypatch)
    _src, lines = _lines(67, 300)
    ref = _reference()
    long_row = MEMBER1.replace(b"?q=1", b"?q=" + b"y" * 4500) + b"\n"
    first = lines[:100]
    second = lines[100:200] + [long_row]
    third = lines[200:]
    p = _processor()
    for part in (first, second):
        g = _group(b"".join(part))
        p.process(g)
        assert _records(g) == _expected(ref, part)
    _per_member(p)
    g = _group(b"".join(third))
    p.process(g)
    assert _records(g) == _expected(ref, third)
    doc = _status()
    assert doc["rows_total"] == 301 and doc["dispatches_total"] == 3
    assert doc["list_program_rows_total"] == 200
    assert doc["device_rows_total"] + doc["walker_rows_total"] \
        + doc["re_rows_total"] + doc["unmatched_rows_total"] == 301
    assert doc["re_rows_total"] == 1 and doc["walker_rows_total"] == 0
    members = [ref.member_of(ln[:-1]) for ln in first + second + third]
    assert doc["member_rows_total"] == [members.count(i) for i in range(4)]
    assert doc["unmatched_rows_total"] == members.count(None)
    assert doc["device_rows_total"] == 300 - members.count(None)
    assert set(doc) == set(grok_mod.COUNTERS) | {"member_rows_total"}
