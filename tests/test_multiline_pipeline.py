"""The multiline Java deployment end to end (PR 31): input_file's Multiline
StartPattern → (inner) split-multiline → processor_parse_regex_tpu →
flusher_file, held EXACTLY to the plain reference
(``perfbench/references/multiline_regex.py``, which imports nothing of the
program) on seeded random records, through both routes of the classify — the
device plane's async leg and the host tiers — and through both walks: the
chain's own and the worker's lane ring.

And the walk itself: a group may hold device work at more than one stage of
its chain; send order stays pop order with three groups in flight; a chain
with one device stage walks exactly as it did.
"""

import importlib.util
import json
import os
import random
import re
import time

import numpy as np
import pytest

from loongcollector_tpu import models
from loongcollector_tpu.input.file.reader import LogFileReader
from loongcollector_tpu.ops import device_stream
from loongcollector_tpu.ops.device_plane import (DevicePlane,
                                                 set_budget_relief)
from loongcollector_tpu.ops.regex.engine import clear_engine_cache
from loongcollector_tpu.pipeline.pipeline import CollectionPipeline
from loongcollector_tpu.pipeline.queue.process_queue_manager import \
    ProcessQueueManager
from loongcollector_tpu.processor import split_multiline
from loongcollector_tpu.runner.processor_runner import (ProcessorRunner,
                                                        WorkerLane)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
START = r"\d{4}-\d{2}-\d{2} .*"
REGEX = r"(\d{4}-\d{2}-\d{2} \d{2}:\d{2}:\d{2}) (\w+) ([\s\S]*)"
KEYS = ["time", "level", "message"]
CHUNK = 4096


def _reference():
    path = os.path.join(REPO, "perfbench", "references", "multiline_regex.py")
    spec = importlib.util.spec_from_file_location("ml_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.make({"start_pattern": START, "regex": REGEX, "keys": KEYS})


def _frame(r: random.Random) -> bytes:
    pkg = ".".join("".join(r.choice("abcdefgh") for _ in range(
        r.randrange(3, 9))) for _ in range(r.randrange(1, 7)))
    return f"\tat {pkg}.Svc.run(Svc.java:{r.randrange(10, 999)})\n".encode()


def _unit(r: random.Random, j: int, width: int, kind: str) -> bytes:
    """One record of about ``width`` bytes: a head line the StartPattern
    matches, frames, and what ``kind`` asks for."""
    stamp = f"2026-03-{r.randrange(1, 29):02d} 10:{r.randrange(60):02d}:" \
            f"{r.randrange(60):02d}"
    level = r.choice(("ERROR", "WARN"))
    if kind == "no_seconds":
        stamp = stamp[:-3]
    elif kind == "dashed_level":
        level = "ERR-OR"
    out = f"{stamp} {level} [exec-{j}] svc - req={j:012d} boom\n".encode()
    blank_at = r.randrange(1, 4) if kind == "blank" else -1
    k = 0
    while len(out) < width:
        out += b"\n" if k == blank_at else _frame(r)
        k += 1
    return out


def _stream(seed: int, n: int = 60):
    """``n`` records of 256–2,048 bytes, one of them longer than a read
    chunk, with a blank line and both reject kinds among them."""
    r = random.Random(seed)
    kinds = ["plain"] * n
    for k, kind in zip(r.sample(range(1, n - 1), 7),
                       ("blank", "blank", "no_seconds", "dashed_level",
                        "no_seconds", "dashed_level", "long")):
        kinds[k] = kind
    return [_unit(r, j, 6000 if kind == "long" else r.randrange(256, 2049),
                  kind) for j, kind in enumerate(kinds)]


@pytest.fixture(autouse=True)
def _fresh_planes():
    prev = models.set_columnar_enabled(True)
    DevicePlane.reset_for_testing()
    device_stream.reset_for_testing()
    split_multiline.reset_for_testing()
    clear_engine_cache()
    yield
    models.set_columnar_enabled(prev)
    DevicePlane.reset_for_testing()
    device_stream.reset_for_testing()
    clear_engine_cache()
    set_budget_relief(None)


def _pipeline(tmp_path, name):
    sink = tmp_path / f"{name}.jsonl"
    p = CollectionPipeline()
    assert p.init(name, {
        "inputs": [{"Type": "input_file",
                    "FilePaths": [str(tmp_path / "app.log")],
                    "Multiline": {"StartPattern": START}}],
        "processors": [{"Type": "processor_parse_regex_tpu", "Regex": REGEX,
                        "Keys": KEYS}],
        "flushers": [{"Type": "flusher_file", "FilePath": str(sink)}]})
    return p, sink


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
        out.append({k: v for k, v in rec.items() if not k.startswith("__")})
    return out


@pytest.mark.parametrize("route", ["device", "host"])
@pytest.mark.parametrize("walk", ["chain", "ring"])
@pytest.mark.parametrize("seed", [3, 20261002])
def test_pipeline_equals_the_plain_reference(tmp_path, monkeypatch, route,
                                             walk, seed):
    if route == "device":
        monkeypatch.setenv("LOONG_NATIVE_T1", "0")
    monkeypatch.setattr(split_multiline, "CARRY_FLUSH_S", 0.0)
    units = _stream(seed)
    log = tmp_path / "app.log"
    log.write_bytes(b"".join(units))
    p, sink = _pipeline(tmp_path, f"ml-{route}-{walk}-{seed}")
    reader = LogFileReader(str(log), chunk_size=CHUNK, multiline_start=START,
                           ml_flush_timeout=0.05, presplit_lines=True)
    runner = ProcessorRunner(ProcessQueueManager(), _Mgr(p), thread_count=1)
    lane = WorkerLane(0, depth=4)
    set_budget_relief(runner._make_relief(lane))

    def feed(group):
        if walk == "ring":
            runner._handle_run(1, [group], lane)
            return
        fin = p.process_begin([group])
        while fin is not None:
            fin = fin()
        p.send([group])

    n_groups = partial = 0
    while True:
        g = reader.read()
        if g is None:
            if reader.offset >= log.stat().st_size:
                break
            # the open record is held in the file until the flush timeout
            time.sleep(0.06)
            continue
        n_groups += 1
        partial += g.get_metadata(
            models.EventGroupMetaKey.ML_PARTIAL_TAIL) == "1"
        feed(g)
    runner._complete_lane(lane)
    assert lane.pending_count() == 0
    assert n_groups > 10 and partial >= 2, \
        "the stream must straddle groups and break at least one record"
    # the stream's last record: no later start line closes it, so the reader
    # shipped it on its flush timeout and the processor holds it until its
    # own — the pipeline's timeout tick delivers it
    before = len(_sink_after_flush(p, sink))
    assert before == len(units) - 1
    for hook in p._drain_hooks:
        hook.flush_timeout()
    got = _sink_after_flush(p, sink)
    ref = _reference()
    want = [ref.expected(u[:-1])[0] for u in units]
    assert len(got) == len(want)
    for k, (g_, w_) in enumerate(zip(got, want)):
        assert g_ == w_, (k, g_, w_)
    assert sum("rawLog" in w for w in want) == 4
    doc = split_multiline.status()[p.name]
    n_lines = sum(u.count(b"\n") for u in units)
    assert doc["lines_total"] == n_lines
    assert doc["records_total"] == len(units)
    assert doc["device_lines_total"] + doc["host_lines_total"] == n_lines
    if route == "device":
        assert doc["host_lines_total"] == 0 and doc["classify_calls"]
    else:
        assert doc["device_lines_total"] == 0 and not doc["classify_calls"]
    assert doc["carry_flushed_total"] == 1
    assert doc["carry_stitched_total"] >= 1       # the record over a chunk
    assert DevicePlane.instance().inflight_bytes() == 0
    p.stop(True)


def _sink_after_flush(p, sink):
    p.flush_batch()
    return _sink_records(sink) if sink.exists() else []


def test_both_stages_ride_the_dispatch_window(tmp_path, monkeypatch):
    """On the device route the classify leaves its chunks in flight (a
    continuation), the merge runs when it completes, and the extract leaves
    its own in flight in its turn: the chain hands back a second
    continuation.  No blocking round trip on the classify path."""
    monkeypatch.setenv("LOONG_NATIVE_T1", "0")
    units = _stream(5, 12)[:10]
    log = tmp_path / "app.log"
    log.write_bytes(b"".join(units) + b"2026-03-01 10:00:00 INFO end\n")
    p, sink = _pipeline(tmp_path, "ml-two-stage")
    ml = p.inner_processors[-1].plugin
    assert ml._async_start

    def no_blocking_match(*a, **k):
        raise AssertionError("the classify path blocked on match_batch")
    monkeypatch.setattr(ml.start, "match_batch", no_blocking_match)
    monkeypatch.setattr(ml.start, "parse_batch", no_blocking_match)
    reader = LogFileReader(str(log), chunk_size=1 << 20,
                           multiline_start=START, presplit_lines=True)
    g = reader.read()
    n_lines = len(g)
    plane = DevicePlane.instance()
    fin = p.process_begin([g])
    assert fin is not None and plane.inflight_bytes() > 0
    assert len(g) == n_lines, "the merge waits for the classify"
    fin2 = fin()
    assert len(g) == len(units), "classify complete: merged"
    assert fin2 is not None and plane.inflight_bytes() > 0, \
        "the extract's chunks are in flight"
    assert fin2() is None and plane.inflight_bytes() == 0
    p.send([g])
    got = _sink_after_flush(p, sink)
    ref = _reference()
    assert got == [ref.expected(u[:-1])[0] for u in units]
    assert p.wait_all_items_in_process_finished(0.1)
    p.stop(True)


def test_spans_and_status_section(tmp_path, monkeypatch):
    """With tracing on, the two legs and the merge leave their spans, the
    device legs nest under the classify's (so its self time is the host's
    own), the match gate compiles under a family of its own, and
    /debug/status has the ``multiline`` section with every counter."""
    from loongcollector_tpu import trace
    from loongcollector_tpu.monitor import exposition
    from loongcollector_tpu.ops import compile_watch
    monkeypatch.setenv("LOONG_NATIVE_T1", "0")
    units = _stream(9, 12)[:8]
    log = tmp_path / "app.log"
    log.write_bytes(b"".join(units) + b"2026-03-01 10:00:00 INFO end\n")
    p, sink = _pipeline(tmp_path, "ml-spans")
    g = LogFileReader(str(log), chunk_size=1 << 20, multiline_start=START,
                      presplit_lines=True).read()
    tracer = trace.enable()
    try:
        p.process([g])
        spans = tracer.finished_spans()
    finally:
        trace.disable()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp)
    for name in ("multiline.classify.dispatch", "multiline.classify.complete",
                 "multiline.merge"):
        assert len(by_name[name]) == 1, name
    stage = "processor.processor_split_multiline_log_string_native"
    disp = by_name["multiline.classify.dispatch"][0]
    assert disp.parent_id == by_name[stage + ".dispatch"][0].span_id
    assert by_name["multiline.merge"][0].parent_id \
        == by_name[stage + ".complete"][0].span_id
    under = {sp.name for sp in spans if sp.parent_id == disp.span_id}
    assert {"device.pack", "device.submit"} <= under
    waits = {sp.name for sp in spans if sp.parent_id
             == by_name["multiline.classify.complete"][0].span_id}
    assert waits & {"device.wait", "device.d2h"}
    assert "multiline.classify" not in by_name      # the one-piece form
    families = compile_watch.compile_status()
    assert families["line_classify"]["compiles"] >= 1
    doc = exposition.collect_status()["multiline"][p.name]
    assert set(split_multiline.COUNTERS) | {"classify_calls"} == set(doc)
    assert doc["lines_total"] == len(g_lines := b"".join(units).split(b"\n")) \
        - 1 and doc["records_total"] == len(units) and g_lines
    assert "multiline" in exposition.STATUS_SECTIONS
    p.stop(True)


@pytest.mark.parametrize("config", [
    {"StartPattern": START, "EndPattern": r".*more"},
    {"StartPattern": START, "ContinuePattern": r"\s+at .*"},
    {"EndPattern": r"\t\.\.\. \d+ more"},
])
def test_other_modes_leave_nothing_in_flight(config, monkeypatch):
    """End / Continue modes classify inside the dispatch leg, as before."""
    from loongcollector_tpu.pipeline.plugin.interface import PluginContext
    from loongcollector_tpu.processor.split_log_string import \
        ProcessorSplitLogString
    monkeypatch.setenv("LOONG_NATIVE_T1", "0")
    data = (b"2026-03-01 10:00:00 ERROR a\n\tat x.Y.z(Y.java:1)\n"
            b"\t... 3 more\n2026-03-01 10:00:01 ERROR b\n\tat q\n")
    twice = []
    for _ in range(2):
        sb = models.SourceBuffer(len(data) + 64)
        g = models.PipelineEventGroup(sb)
        g.add_raw_event(1700000000).set_content(sb.copy_string(data))
        ctx = PluginContext("modes")
        sp = ProcessorSplitLogString()
        sp.init({}, ctx)
        ml = split_multiline.ProcessorSplitMultilineLogString()
        assert ml.init({"Multiline": config}, ctx)
        assert not ml._async_start
        sp.process(g)
        if twice:
            ml.process(g)
        else:
            assert ml.process_dispatch(g) is None
        cols = g.columns
        arena = g.source_buffer.as_array()
        twice.append([bytes(arena[o:o + n].tobytes())
                      for o, n in zip(cols.offsets, cols.lengths)])
    assert twice[0] == twice[1] and len(twice[0]) < data.count(b"\n")
    assert DevicePlane.instance().inflight_bytes() == 0


# -- the walk: lane ring with more than one device stage -----------------------

class _Staged:
    """A pipeline whose every group holds device work at ``stages`` stages
    in turn; ``log`` records what happened, in order."""

    name = "staged"

    def __init__(self, stages: int, log: list):
        self.stages = stages
        self.log = log

    def process_begin(self, groups):
        k = groups[0]
        self.log.append(("dispatch", k, 0))

        def step(stage):
            def finish():
                self.log.append(("complete", k, stage))
                if stage + 1 == self.stages:
                    return None
                self.log.append(("dispatch", k, stage + 1))
                return step(stage + 1)
            return finish
        return step(0)

    def send(self, groups):
        self.log.append(("send", groups[0]))


class _Int(int):
    """A group that is its own sequence number."""

    def __len__(self):
        return 1

    def data_size(self):
        return 1


def _drive(stages: int, depth: int, n: int):
    log: list = []
    runner = ProcessorRunner(ProcessQueueManager(),
                             _Mgr(_Staged(stages, log)), thread_count=1)
    lane = WorkerLane(0, depth=depth)
    for k in range(n):
        runner._handle_run(1, [_Int(k)], lane)
    in_flight = lane.pending_count()
    runner._complete_lane(lane)
    runner.metrics.mark_deleted()
    return log, in_flight


def test_two_stage_walk_keeps_send_order_with_three_groups_in_flight():
    log, in_flight = _drive(stages=2, depth=4, n=12)
    assert in_flight == 3
    assert [e[1] for e in log if e[0] == "send"] == list(range(12))
    # each stage of each group completes once, stage 0 before stage 1, and
    # every stage's completions are in pop order (the merge's carry needs it)
    for stage in (0, 1):
        assert [e[1] for e in log if e[0] == "complete" and e[2] == stage] \
            == list(range(12))
    at = {e: i for i, e in enumerate(log)}
    for k in range(12):
        assert at[("complete", k, 0)] < at[("dispatch", k, 1)] \
            < at[("complete", k, 1)] < at[("send", k)]
    # the overlap, once the ring has filled: between a group's second
    # dispatch and its completion the worker dispatched another group —
    # nobody waits for a stage the moment it was dispatched
    for k in range(3, 9):
        between = log[at[("dispatch", k, 1)] + 1:at[("complete", k, 1)]]
        assert any(e[0] == "dispatch" and e[2] == 0 for e in between), k


def test_a_chain_with_one_device_stage_walks_as_before():
    log, in_flight = _drive(stages=1, depth=3, n=6)
    assert in_flight == 2
    # dispatch N+1, then complete and send the oldest once the ring is
    # full: the walk of every accepted cell, event for event
    want = [("dispatch", 0, 0), ("dispatch", 1, 0)]
    for k in range(2, 6):
        want += [("dispatch", k, 0), ("complete", k - 2, 0),
                 ("send", k - 2)]
    want += [("complete", 4, 0), ("send", 4), ("complete", 5, 0),
             ("send", 5)]
    assert log == want


def test_relief_inside_a_step_never_sends_ahead_of_the_head():
    """A budget wait inside a group's second dispatch runs the relief hook
    from inside the step: the groups behind may advance and finish, but
    none is sent before the group being stepped."""
    log: list = []
    pipe = _Staged(2, log)
    runner = ProcessorRunner(ProcessQueueManager(), _Mgr(pipe),
                             thread_count=1)
    lane = WorkerLane(0, depth=4)
    relief = runner._make_relief(lane)
    real_begin = pipe.process_begin

    def begin(groups):
        fin0 = real_begin(groups)
        k = groups[0]
        if k != 0:
            return fin0

        def fin():
            # group 0's second dispatch "waits for budget": relieve until
            # the ring has nothing left to give
            nxt = fin0()
            while relief():
                pass
            return nxt
        return fin
    pipe.process_begin = begin
    for k in range(3):
        runner._handle_run(1, [_Int(k)], lane)
    assert lane.pending_count() == 3
    runner._step_oldest(lane)             # steps group 0, relief inside
    sends = [e[1] for e in log if e[0] == "send"]
    assert sends == [], "nothing may be sent while the head is mid-step"
    assert ("complete", 1, 1) in log and ("complete", 2, 1) in log
    runner._complete_lane(lane)
    assert [e[1] for e in log if e[0] == "send"] == [0, 1, 2]
    runner.metrics.mark_deleted()
