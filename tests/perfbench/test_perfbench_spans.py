"""Rehearsal of the readers PR 25 added (perfbench/benchlib/spans.py and the
metric files over it): each on a hand-made ``obs`` — a number where its span
or counter is there, None (never 0) where it is not, as with a program from
before that PR — and one traced run of each saturated cell on the CPU in
which every new metric of the cell is reported.  CPU numbers are not looked
at: only that they are there."""

import os
import sys

# the benchmark's library lives beside the benchmark, not in the program
sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench"))

import json
import subprocess

import numpy as np
import pytest

from benchlib import agent as agentmod
from benchlib import spans, spec

REPO = spec.ROOT
BM = spec.load_benchmark()

#: what this PR added to BENCHMARK.json, by the reader's source
NEW = {
    "read_stage_s_per_GB", "reader_blocked_share.sat",
    "reader_blocked_share.tail", "throttled_round_share",
    "device_wait_s_per_GB", "device_copy_s_per_GB", "serialize_s_per_GB",
    "sink_write_s_per_GB", "pause_share.sat", "pause_share.tail",
    "pause_over_32ms", "device_program_s_per_GB", "backend_init_s",
    "first_dispatch_s",
}


def _metrics_text(scale: float) -> str:
    """A /metrics page with two pause histograms, as the agent renders them
    (cumulative buckets); ``scale`` multiplies every count."""
    def hist(name, counts, total_s):
        lines, cum = [], 0
        for le, c in counts:
            cum += int(c * scale)
            lines.append('loong_span_seconds_bucket{category="trace",'
                         f'name="{name}",le="{le}"}} {cum}')
        lines.append('loong_span_seconds_bucket{category="trace",'
                     f'name="{name}",le="+Inf"}} {cum}')
        lines.append('loong_span_seconds_sum{category="trace",'
                     f'name="{name}"}} {total_s * scale}')
        lines.append('loong_span_seconds_count{category="trace",'
                     f'name="{name}"}} {cum}')
        return lines
    return "\n".join(
        hist("runtime.gc", [("0.000128", 100), ("0.032768", 10),
                            ("0.065536", 2), ("0.131072", 1)], 0.5)
        + hist("ledger.audit", [("0.001024", 40), ("0.065536", 1)], 0.1)
        + hist("processor.x", [("0.065536", 1000)], 50.0)) + "\n"


def _obs(with_new: bool) -> dict:
    """A traced saturated window of 10 s delivering 1 GB, its slice of 2 s
    delivering 0.2 GB; ``with_new`` False is the parent's program: the old
    spans only, no histograms, no new status sections."""
    line = 500
    tail_t = np.array([0.0, 100.0, 101.0, 103.0, 110.0])
    last_seq = np.array([-1, -1, 199_999, 599_999, 1_999_999])
    old = [["pipeline.process", 101.0, 0.9, 1, None, {}],
           ["processor.regex.complete", 101.1, 0.5, 2, 1, {}],
           ["device.roundtrip", 101.0, 0.3, 3, 1, {"nbytes": 524288}],
           ["flusher.send", 101.6, 0.3, 4, 1, {}]]
    new = [["input.file.read", 101.0, 0.02, 10, None, {}],
           ["device.pack", 101.0, 0.01, 11, 2, {}],
           ["device.submit", 101.01, 0.02, 12, 2, {}],
           ["device.acquire", 101.03, 0.10, 13, 2, {}],
           ["device.wait", 101.04, 0.06, 14, 13, {}],   # drained meanwhile
           ["device.wait", 101.2, 0.14, 15, 2, {}],
           ["device.d2h", 101.34, 0.03, 16, 2, {}],
           ["flusher.serialize", 101.6, 0.08, 17, 4, {}],
           ["flusher.write", 101.7, 0.12, 18, 4, {}]]
    events = [["/device:TPU:0", "XLA Ops", "%extract.1 = x", 5e8, 1e6],
              ["/host:CPU", "python", "perfbench_mark", 0.0, 10.0]]
    status0 = {"uptime_s": 20.0}
    status1 = {"uptime_s": 32.0}
    if with_new:
        events += [
            ["/device:TPU:0", "XLA Modules",
             "jit_loong_extract_pallas(123)", 1e8, 2e7],
            ["/device:TPU:0", "XLA Modules",
             "jit_loong_extract_pallas(123)", 9e8, 2e7],
            ["/device:TPU:0", "XLA Modules", "jit_probe(9)", 3e8, 5e7],
            ["/device:TPU:0", "XLA Modules",
             "jit_loong_extract_pallas(123)", 3e9, 2e7]]   # after the slice
        status0["file_input"] = {
            "rounds_total": 100, "rounds_throttled_total": {"3": 1, "8": 0},
            "throttle_sleep_seconds_total": 0.1, "reads_total": 50,
            "read_bytes_total": 1000, "reads_blocked_total": 10,
            "push_rejected_total": 0}
        status1["file_input"] = {
            "rounds_total": 300, "rounds_throttled_total": {"3": 11, "8": 10},
            "throttle_sleep_seconds_total": 2.1, "reads_total": 150,
            "read_bytes_total": 3000, "reads_blocked_total": 280,
            "push_rejected_total": 30}
        status1["startup"] = {"imports_done": 0.5, "backend_up": 11.0,
                              "native_loaded": 11.2,
                              "pipelines_started": 11.5,
                              "first_dispatch": 13.75}
    return {
        "t0": 100.0, "t1": 110.0, "line_bytes": line,
        "tail": {"t": tail_t, "last_seq": last_seq},
        "slice": (101.0, 103.0),
        "spans": old + (new if with_new else []),
        "trace": {"events": events, "lo_ns": 0.0, "hi_ns": 2e9},
        "status0": status0, "status1": status1,
        "metrics0": agentmod.parse_metrics(
            _metrics_text(1.0) if with_new else ""),
        "metrics1": agentmod.parse_metrics(
            _metrics_text(3.0) if with_new else ""),
    }


#: the reader's value on the hand-made window (0.2 GB in the slice; 12 s
#: between the scrapes)
EXPECTED = {
    "read_stage_s_per_GB": 0.02 / 0.2,
    "reader_blocked_share.sat": 300 / 400,
    "reader_blocked_share.tail": 300 / 400,
    "throttled_round_share": 20 / 200,
    # both waits, and the acquire's self time (0.10 less the 0.06 inside it)
    "device_wait_s_per_GB": (0.06 + 0.14 + 0.04) / 0.2,
    "device_copy_s_per_GB": (0.01 + 0.02 + 0.03) / 0.2,
    "serialize_s_per_GB": 0.08 / 0.2,
    "sink_write_s_per_GB": 0.12 / 0.2,
    "pause_share.sat": (1.0 + 0.2) / 12.0,
    "pause_share.tail": (1.0 + 0.2) / 12.0,
    # over 32.768 ms: gc 2 + 1 and audit 1 per unit of scale, times 2
    "pause_over_32ms": 8.0,
    "device_program_s_per_GB": 0.04 / 0.2,
    "backend_init_s": 10.5,
    "first_dispatch_s": 2.25,
}


def test_this_prs_entries_are_the_ones_the_readers_expect():
    assert set(EXPECTED) == NEW
    declared = {m["name"]: m for m in BM["per_layer"]}
    assert NEW <= set(declared)
    for name in NEW:
        # by name, once, wherever later PRs put it: not by its place
        assert [m["name"] for m in BM["per_layer"]].count(name) == 1, name
        assert declared[name]["workloads"], name      # every one lists them
        assert spec.load_module("metrics", name).read  # its reader is there


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_gives_a_number_where_the_program_has_the_source(name):
    value = spec.load_module("metrics", name).read(_obs(True))
    assert value == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_gives_nothing_on_a_program_without_it(name):
    # the parent's program: old spans, no histograms, no new sections.
    # None, never 0, and no exception: the line then leaves the metric out
    assert spec.load_module("metrics", name).read(_obs(False)) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_gives_nothing_on_an_untraced_run(name):
    obs = _obs(False)
    obs.update(spans=None, trace=None, slice=None, status0={}, status1={},
               metrics0={}, metrics1={})
    assert spec.load_module("metrics", name).read(obs) is None


def test_module_seconds_by_name_inside_the_slice_only():
    total, by = spans.module_seconds(_obs(True), "jit_loong_")
    assert by == {"jit_loong_extract_pallas": pytest.approx(0.04)}
    assert total == pytest.approx(0.04)
    assert spans.module_seconds(_obs(True), "jit_nosuch_") is None


def test_makeup_of_a_stage_is_its_self_time_and_its_children_by_name():
    found = spans.makeup(_obs(True), ".complete")
    assert found["seconds"] == pytest.approx(0.5)
    # the acquire's own child is not the stage's: children one level down
    assert found["children"] == {
        "device.pack": pytest.approx(0.01),
        "device.submit": pytest.approx(0.02),
        "device.acquire": pytest.approx(0.10),
        "device.wait": pytest.approx(0.14),
        "device.d2h": pytest.approx(0.03)}
    assert found["self"] + sum(found["children"].values()) \
        == pytest.approx(found["seconds"])
    assert spans.makeup(_obs(True), ".nosuch") is None


def test_an_empty_window_of_counters_is_nothing_not_zero():
    obs = _obs(True)
    obs["status0"]["file_input"] = dict(obs["status1"]["file_input"])
    assert spans.reader_blocked_share(obs) is None
    assert spans.throttled_round_share(obs) is None
    obs["metrics0"] = obs["metrics1"]              # nothing paused: a zero
    assert spans.pause_share(obs) == 0.0
    assert spans.pauses_over(obs, 0.032768) == 0.0


@pytest.mark.parametrize("workload", ["regex512.backlog",
                                      "filter512.backlog"])
def test_traced_saturated_cell_reports_every_new_metric(workload):
    # the device path forced on the CPU (the routing probe would keep every
    # group on the host walker here): the dispatch legs then exist
    env = dict(os.environ, LOONG_NATIVE_T1="0", LOONG_DEVICE_MIN_BYTES="0")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", workload, "--seed", "2147483661", "--seconds", "2.5",
         "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["correct"] is True, doc["checks"]
    want = {m["name"] for m in spec.metrics_of_cell(BM, workload,
                                                    "per_layer")} & NEW
    # the CPU's profile has no device plane: what reads the device trace
    # finds nothing there, as extract_* does
    want.discard("device_program_s_per_GB")
    assert len(want) == 11
    missing = want - set(doc["metrics"])
    assert not missing, missing
    for name in want:
        assert isinstance(doc["metrics"][name]["value"], float)
    assert "device_program_s_per_GB" not in doc["metrics"]
