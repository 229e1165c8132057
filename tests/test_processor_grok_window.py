"""processor_grok on the asynchronous path (PR 34): an ordered ``Match`` list
through ``process_dispatch`` / ``process_complete`` and the one dispatch
window, held EXACTLY to the plain reference
(``perfbench/references/grok_match_list.py``, which imports nothing of the
program) on seeded lines of every kind the benchmark's configuration makes —
field by field and in order, sparse fields and ``rawLog`` rows included —,
through the device route and the host route, with several groups in flight,
with a CPU-tier member and an over-long row in the list's way, and under a
budget too small for two handles at once.
"""

import json
import os
import sys

import numpy as np
import pytest
import yaml

from loongcollector_tpu import models, trace
from loongcollector_tpu.monitor import exposition
from loongcollector_tpu.ops import device_stream
from loongcollector_tpu.ops.device_plane import (DevicePlane,
                                                 set_budget_relief)
from loongcollector_tpu.ops.regex.engine import (clear_engine_cache,
                                                 get_engine)
from loongcollector_tpu.ops.regex.grok import expand
from loongcollector_tpu.ops.regex.program import PatternTier
from loongcollector_tpu.pipeline.pipeline import CollectionPipeline
from loongcollector_tpu.pipeline.plugin.interface import PluginContext
from loongcollector_tpu.pipeline.queue.process_queue_manager import \
    ProcessQueueManager
from loongcollector_tpu.processor import grok as grok_mod
from loongcollector_tpu.processor.grok import ProcessorGrok
from loongcollector_tpu.processor.split_log_string import \
    ProcessorSplitLogString
from loongcollector_tpu.runner.processor_runner import (ProcessorRunner,
                                                        WorkerLane)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "perfbench")
CONFIG_DIR = os.path.join(BENCH, "configs", "file_grok_nginx")
SOURCE = {"line_bytes": 256, "pool": 256,
          "mix": {"member1": 60, "member2": 10, "member3": 10, "member4": 10,
                  "unmatched": 10},
          "status_mix": {"200": 80, "304": 15, "404": 5}}
# a member no device tier holds (a backreference), ahead of the cell's list
ECHO = r"(?P<word>[a-z]+) (?P=word) %{NUMBER:count}"


def _load(kind, name):
    """A file of the benchmark (line source, plain reference), found as the
    benchmark finds it."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    from benchlib import spec
    return spec.load_module(kind, name)


def _match_list():
    """The cell's Match list, read out of its pipeline.yaml."""
    text = open(os.path.join(CONFIG_DIR, "pipeline.yaml")).read()
    doc = yaml.safe_load(text.replace("{log_path}", "x")
                         .replace("{sink_path}", "y"))
    (proc,) = [p for p in doc["processors"] if p["Type"] == "processor_grok"]
    return proc["Match"]


MATCH = _match_list()


def _reference(match=None):
    return _load("references", "grok_match_list").make(
        {"match": match or MATCH})


def _lines(seed, n, first=0):
    src = _load("sources", "nginx_templates").make(SOURCE, seed)
    return src, [src.line(j) for j in range(first, first + n)]


@pytest.fixture(autouse=True)
def _fresh_planes(monkeypatch):
    # the benchmark's single-device setting: an unbound parse in a test
    # process with eight virtual devices would be a sharded one
    monkeypatch.setenv("LOONG_SHARDED", "0")
    prev = models.set_columnar_enabled(True)
    DevicePlane.reset_for_testing()
    device_stream.reset_for_testing()
    grok_mod.reset_for_testing()
    clear_engine_cache()
    yield
    models.set_columnar_enabled(prev)
    DevicePlane.reset_for_testing()
    device_stream.reset_for_testing()
    clear_engine_cache()
    set_budget_relief(None)


def _device_route(monkeypatch):
    monkeypatch.setenv("LOONG_NATIVE_T1", "0")
    monkeypatch.setenv("LOONG_DEVICE_MIN_BYTES", "0")
    clear_engine_cache()


def _processor(match=None, name="grok-window"):
    p = ProcessorGrok()
    assert p.init({"Match": match or MATCH}, PluginContext(name))
    return p


def _per_member(p):
    """The test-only seam onto the per-member path (PR 35): the cell's
    all-SEGMENT list rides ONE program a group on the device route
    (tests/test_processor_grok_list_program.py), and the tests below exist
    for what a group with several handles in flight needs — the path every
    list with a member no device tier holds still takes."""
    assert p._list_ok, "the cell's list is served by the list program"
    p._list_ok = False
    return p


def _group(data: bytes):
    sb = models.SourceBuffer(len(data) + 64)
    g = models.PipelineEventGroup(sb)
    g.add_raw_event(1700000000).set_content(sb.copy_string(data))
    split = ProcessorSplitLogString()
    split.init({}, PluginContext("grok-window"))
    split.process(g)
    assert g.columns is not None
    return g


def _records(g):
    """The group's rows as ordered (key, value) lists, absent fields left
    out: what the serializer would write."""
    cols = g.columns
    arena = g.source_buffer.as_array()
    out = []
    for i in range(len(cols)):
        rec = []
        for key, (offs, lens) in cols.fields.items():
            if lens[i] >= 0:
                o = int(offs[i])
                rec.append((key, arena[o:o + int(lens[i])].tobytes()
                            .decode("latin-1")))
        out.append(rec)
    return out


def _expected(ref, lines):
    return [list(ref.expected(ln.rstrip(b"\n"))[0].items()) for ln in lines]


# -- the cell's list ------------------------------------------------------------------

def test_every_member_of_the_cells_list_is_on_the_segment_tier():
    """What keeps Python's ``re`` out of the benchmark's window: each member
    of the configuration's Match list compiles to the SEGMENT tier, and the
    four fuse into one full-match automaton that holds them all."""
    assert len(MATCH) == 4
    engines = [get_engine(expand(m)) for m in MATCH]
    assert [e.tier for e in engines] == [PatternTier.SEGMENT] * 4
    assert [e.num_caps for e in engines] == [13, 13, 11, 9]
    p = _processor()
    fs = p._fused_set
    assert fs is not None and fs.n_fused == 4 and fs.fdfa.device_ok
    assert fs.fdfa.num_states == 94
    assert p._keys[-2:] == ["upstream_response_time", "upstream_raw"]
    assert len(p._keys) == 14
    cfg = json.load(open(os.path.join(CONFIG_DIR, "config.json")))
    assert cfg["reference"]["match"] == MATCH


def test_source_makes_every_kind_and_members_one_and_two_overlap():
    src, lines = _lines(11, 256)
    ref = _reference()
    kinds = {k["kind"] for k in src.kinds}
    assert kinds == {"member1", "member2", "member3", "member4", "unmatched"}
    want = {"member1": 0, "member2": 1, "member3": 2, "member4": 3,
            "unmatched": None}
    for k in range(src.pool):
        line = src.templates[k].tobytes()[:-1]
        assert ref.member_of(line) == want[src.kinds[k]["kind"]]
        if src.kinds[k]["kind"] == "member1":
            # the list's order decides: member 2 alone would take it too
            assert ref.members[1].fullmatch(line) is not None
    assert any(k["bytes"] == "-" for k in src.kinds)        # a sparse field


# -- window path == process == reference -----------------------------------------------

@pytest.mark.parametrize("route", ["device", "host"])
@pytest.mark.parametrize("seed", [5, 2147483659])
def test_dispatch_complete_equals_process_equals_reference(monkeypatch, route,
                                                           seed):
    if route == "device":
        _device_route(monkeypatch)
    _src, lines = _lines(seed, 300)
    data = b"".join(lines)
    want = _expected(_reference(), lines)
    p = _processor()
    plane = DevicePlane.instance()

    g1 = _group(data)
    token = p.process_dispatch(g1)
    if route == "device":
        assert token is not None and plane.inflight_bytes() > 0
        assert g1.columns.parse_ok is None, "nothing applied at dispatch"
        p.process_complete(g1, token)
    else:
        assert token is None, "host subsets finish in the dispatch leg"
    assert plane.inflight_bytes() == 0
    g2 = _group(data)
    p.process(g2)
    got1, got2 = _records(g1), _records(g2)
    assert len(got1) == len(want) == 300
    for k, (a, b, w) in enumerate(zip(got1, got2, want)):
        assert a == w, (k, a, w)        # field by field, in order
        assert b == w, (k, b, w)
    n_raw = sum(w[0][0] == "rawLog" for w in want)
    assert n_raw > 0 and sum(len(w) == 12 for w in want) > 0   # 304: no bytes
    assert g1.columns.parse_ok.sum() == 300 - n_raw
    assert g1.columns.content_consumed

    doc = grok_mod.status()["grok-window"]
    assert doc["rows_total"] == 600 and doc["dispatches_total"] == 2
    assert doc["unmatched_rows_total"] == 2 * n_raw
    assert doc["device_rows_total"] + doc["walker_rows_total"] \
        + doc["re_rows_total"] + doc["unmatched_rows_total"] == 600
    assert doc["re_rows_total"] == 0
    ref = _reference()
    members = [ref.member_of(ln[:-1]) for ln in lines]
    assert doc["member_rows_total"] == [2 * members.count(i)
                                        for i in range(4)]
    if route == "device":
        assert doc["walker_rows_total"] == 0
        assert doc["device_rows_total"] == 600 - 2 * n_raw
    else:
        assert doc["device_rows_total"] == 0


def test_applied_out_of_order_would_show(monkeypatch):
    """The reference catches a list applied in another order: with members
    1 and 2 swapped every member-1 line takes ``upstream_raw``."""
    _src, lines = _lines(7, 120)
    swapped = [MATCH[1], MATCH[0]] + MATCH[2:]
    p = _processor(swapped, "grok-swapped")
    g = _group(b"".join(lines))
    p.process(g)
    want = _expected(_reference(), lines)
    differing = sum(a != w for a, w in zip(_records(g), want))
    assert differing == sum(w[-1][0] == "upstream_response_time"
                            for w in want) > 0


# -- several groups in flight -------------------------------------------------------------

class _Mgr:
    def __init__(self, pipeline):
        self.pipeline = pipeline

    def find_pipeline_by_queue_key(self, key):
        return self.pipeline


def _sink_records(sink):
    out = []
    for ln in sink.read_text().splitlines():
        rec = json.loads(ln)
        rec.pop("__time__", None)
        out.append([(k, v) for k, v in rec.items()
                    if not k.startswith("__")])
    return out


def test_groups_in_flight_complete_in_source_order(tmp_path, monkeypatch):
    """Through the worker's lane ring on the device route: four groups are
    dispatched before the first completes, each holds the handle of every
    member of the list, and the sink gets every line once, in order."""
    _device_route(monkeypatch)
    _src, lines = _lines(13, 1000)
    sink = tmp_path / "grok.jsonl"
    p = CollectionPipeline()
    assert p.init("grok-ring", {
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
    doc = grok_mod.status()["grok-ring"]
    assert doc["rows_total"] == 1000 and doc["dispatches_total"] == 8
    assert doc["device_rows_total"] + doc["unmatched_rows_total"] == 1000
    p.stop(True)


def test_a_budget_too_small_for_two_handles_does_not_deadlock(monkeypatch):
    """Every member's subset rides the window; the plane's budget holds one
    slot.  A later member's dispatch gives back what the earlier members of
    the SAME group hold (no runner hook can see those)."""
    _device_route(monkeypatch)
    plane = DevicePlane.reset_for_testing(budget_bytes=40 * 1024)
    _src, lines = _lines(17, 400)
    p = _per_member(_processor())
    g = _group(b"".join(lines))
    token = p.process_dispatch(g)
    p.process_complete(g, token)
    assert plane.inflight_bytes() == 0
    assert _records(g) == _expected(_reference(), lines)


def test_a_failed_member_gives_the_others_chunks_back(monkeypatch):
    _device_route(monkeypatch)
    _src, lines = _lines(19, 200)
    p = _per_member(_processor())
    g = _group(b"".join(lines))
    engine = p._engines[2][0]

    def boom(*a, **k):
        raise RuntimeError("member 3 cannot dispatch")
    monkeypatch.setattr(engine, "parse_batch_async", boom)
    with pytest.raises(RuntimeError):
        p.process_dispatch(g)
    assert DevicePlane.instance().inflight_bytes() == 0


# -- where Python's re still meets a row ---------------------------------------------------

@pytest.mark.parametrize("route", ["device", "host"])
def test_cpu_tier_member_and_overlong_row(monkeypatch, route):
    """A list with a member on the CPU tier (the automaton cannot hold it,
    so it probes what is unmatched at its turn and is waited for) and a row
    over the largest bucket: the reference's records, and the counters say
    how many rows met ``re``."""
    if route == "device":
        _device_route(monkeypatch)
    match = [MATCH[0], ECHO] + MATCH[1:]
    assert get_engine(expand(ECHO)).tier is PatternTier.CPU
    _src, lines = _lines(23, 150)
    first = next(ln for ln in lines if _reference().member_of(ln[:-1]) == 0)
    long_line = first[:-1].replace(b"?", b"?pad=" + b"x" * 5000 + b"&", 1) \
        + b"\n"
    assert len(long_line) > 4096 + 256
    extra = [b"abc abc 17\n", b"abc abd 17\n", long_line, b"zz zz -1.5\n"]
    lines = lines[:70] + extra[:2] + lines[70:] + extra[2:]
    ref = _reference(match)
    want = _expected(ref, lines)
    assert [k for k, _ in want[70]] == ["word", "count"]
    assert want[71][0][0] == "rawLog"
    assert want[-2][-1][0] == "upstream_response_time"      # the long row

    p = _processor(match, "grok-cpu-tier")
    assert p._fused_set is None or 1 not in p._fused_set.bit_of
    tracer = trace.enable()
    try:
        g = _group(b"".join(lines))
        token = p.process_dispatch(g)
        p.process_complete(g, token)
        spans = tracer.finished_spans()
    finally:
        trace.disable()
    got = _records(g)
    for k, (a, w) in enumerate(zip(got, want)):
        assert a == w, (k, a, w)
    assert DevicePlane.instance().inflight_bytes() == 0
    doc = grok_mod.status()["grok-cpu-tier"]
    members = [ref.member_of(ln[:-1]) for ln in lines]
    n = len(lines)
    assert doc["rows_total"] == n
    assert doc["member_rows_total"] == [members.count(i) for i in range(5)]
    assert doc["unmatched_rows_total"] == members.count(None)
    # the echo member's two rows, and on the device route the long row
    # (the host walker has no width limit and takes it there)
    assert doc["re_rows_total"] == (3 if route == "device" else 2)
    assert doc["device_rows_total"] + doc["walker_rows_total"] \
        + doc["re_rows_total"] + doc["unmatched_rows_total"] == n
    assert any(sp.name == "grok.re_rows" for sp in spans)


# -- spans and the status section ----------------------------------------------------------

def test_spans_nest_under_the_stage_and_status_has_the_section(tmp_path,
                                                               monkeypatch):
    _device_route(monkeypatch)
    _src, lines = _lines(29, 200)
    sink = tmp_path / "grok.jsonl"
    p = CollectionPipeline()
    assert p.init("grok-spans", {
        "inputs": [{"Type": "input_file",
                    "FilePaths": [str(tmp_path / "access.log")]}],
        "processors": [{"Type": "processor_grok", "Match": MATCH}],
        "flushers": [{"Type": "flusher_file", "FilePath": str(sink)}]})
    (grok,) = [inst.plugin for inst in p.processors
               if isinstance(inst.plugin, ProcessorGrok)]
    _per_member(grok)
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
    for name in ("grok.classify", "grok.members.dispatch", "grok.apply"):
        assert len(by_name[name]) == 1, name
    assert "grok.re_rows" not in by_name
    stage = "processor.processor_grok"
    dispatch = by_name[stage + ".dispatch"][0]
    assert by_name["grok.classify"][0].parent_id == dispatch.span_id
    members = by_name["grok.members.dispatch"][0]
    assert members.parent_id == dispatch.span_id
    assert by_name["grok.apply"][0].parent_id \
        == by_name[stage + ".complete"][0].span_id
    under = {sp.name for sp in spans if sp.parent_id == members.span_id}
    assert {"device.pack", "device.submit"} <= under
    doc = exposition.collect_status()["grok"]["grok-spans"]
    assert set(doc) == set(grok_mod.COUNTERS) | {"member_rows_total"}
    assert doc["rows_total"] == 200 and len(doc["member_rows_total"]) == 4
    assert "grok" in exposition.STATUS_SECTIONS
    p.stop(True)


def test_a_list_of_one_and_a_list_that_does_not_fuse(monkeypatch):
    """One member: no classify, one handle.  Members the automaton cannot
    hold: the per-pattern probe in Match order."""
    _src, lines = _lines(31, 100)
    data = b"".join(lines)
    one = _processor([MATCH[2]], "grok-one")
    assert one._fused_set is None
    g = _group(data)
    one.process(g)
    assert _records(g) == _expected(_reference([MATCH[2]]), lines)
    probing = _processor(MATCH, "grok-probing")
    probing._fused_set = None
    g = _group(data)
    probing.process(g)
    assert _records(g) == _expected(_reference(), lines)
    doc = grok_mod.status()["grok-probing"]
    assert doc["rows_total"] == 100 == doc["walker_rows_total"] \
        + doc["unmatched_rows_total"]


def test_a_row_engine_and_automaton_disagree_on_is_decided_by_re(monkeypatch):
    """The bug net: the classify gives a row to a member and the member's
    engine does not take it.  ``re`` decides the row from that member on, so
    the record is still the reference's, and the counters say it happened."""
    _src, lines = _lines(37, 120)
    ref = _reference()
    p = _per_member(_processor(name="grok-disagree"))
    engine = p._engines[0][0]
    real = engine.parse_batch_async

    def refusing(arena, offsets, lengths, *a, **k):
        pending = real(arena, offsets, lengths, *a, **k)
        res = pending.result()
        res.ok[:3] = False                   # the engine "misses" three rows
        return pending
    monkeypatch.setattr(engine, "parse_batch_async", refusing)
    g = _group(b"".join(lines))
    p.process(g)
    assert _records(g) == _expected(ref, lines)
    doc = grok_mod.status()["grok-disagree"]
    assert doc["re_rows_total"] == 3
    assert doc["device_rows_total"] + doc["walker_rows_total"] \
        + doc["re_rows_total"] + doc["unmatched_rows_total"] == 120
    assert doc["member_rows_total"][0] == [ref.member_of(ln[:-1])
                                           for ln in lines].count(0)


def test_the_row_path_counts_its_rows_as_re_rows():
    """A group that is not columnar goes row by row through ``re``: the
    status section says so (``re_rows_total``), and the counters add up."""
    _src, lines = _lines(41, 60)
    ref = _reference()
    members = [ref.member_of(ln[:-1]) for ln in lines]
    sb = models.SourceBuffer(64 * 1024)
    g = models.PipelineEventGroup(sb)
    for ln in lines:
        g.add_log_event(1700000000).set_content(b"content",
                                                sb.copy_string(ln[:-1]))
    p = _processor(name="grok-rows")
    p.process(g)
    doc = grok_mod.status()["grok-rows"]
    hit = sum(m is not None for m in members)
    assert doc["rows_total"] == 60
    assert doc["re_rows_total"] == hit == sum(doc["member_rows_total"])
    assert doc["unmatched_rows_total"] == 60 - hit
    assert doc["device_rows_total"] == doc["walker_rows_total"] == 0
    assert doc["member_rows_total"] == [members.count(i) for i in range(4)]
