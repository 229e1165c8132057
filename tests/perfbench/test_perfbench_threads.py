"""Work against waiting (PR 36): the readers of ``cpu_s`` / ``tid`` in the
program's spans and of /debug/status ``threads`` give a number on a made-up
window that has their source and nothing — never 0 — on one that has not (the
parent's program has neither), the seven entries are found by name with the
cells the issue gives them, and one traced CPU rehearsal of ``regex512.backlog``
prints the four span-sourced metrics and the counter-sourced ones.

Two of the nine readers have no entry in ``BENCHMARK.json`` yet:
``worker_runq_share`` and ``worker_switches_per_MB`` read ``schedstat``'s
run-queue wait and ``status``'s voluntary switches, which the kernel of the
machines that hold the chip does not give (my chip run, PR 36, call 1: the
``threads`` rows there have ``cpu_s`` and ``last_cpu`` alone), and an entry lists
the cells in which its reader finds something to read.  They are held here on
the fields a Linux kernel gives, for the ``benchmark`` PR that can list them."""

import json
import os
import subprocess
import sys

# the benchmark's library lives beside the benchmark, not in the program
sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench"))

import numpy as np
import pytest

from benchlib import spec, threads

BM = spec.load_benchmark()
REPO = spec.ROOT
SAT = ["regex512.backlog", "filter512.backlog", "json1k_filter.backlog",
       "multiline_java.backlog", "grok_nginx.backlog"]
#: the cells the seven entries list at the least: SAT, and the HTTP-event cell
#: on its own mix (``backlog128``)
CELLS = SAT + ["http_classify.backlog"]
SPAN_SOURCED = ["proc_stage_cpu_s_per_GB", "device_copy_cpu_s_per_GB",
                "worker_offcpu_share", "reader_round_s_per_GB"]
COUNTER_SOURCED = ["worker_cpu_share", "reader_cpu_share",
                   "enqueue_blocked_share"]
#: readers without an entry: what they read, the chip's host does not give
UNLISTED = ["worker_runq_share.sat", "worker_runq_share.tail",
            "worker_switches_per_MB"]
ENTRIES = {
    # name: (unit, better, source, layer, moves, cells)
    "proc_stage_cpu_s_per_GB": ("s/GB", "lower", "program_span",
                                "processors", "delivered_MBps", CELLS),
    "device_copy_cpu_s_per_GB": ("s/GB", "lower", "program_span", "dispatch",
                                 "delivered_MBps", CELLS),
    "worker_offcpu_share": ("share", "lower", "program_span", "host",
                            "delivered_MBps", CELLS),
    "reader_round_s_per_GB": ("s/GB", "lower", "program_span", "file input",
                              "delivered_MBps", CELLS),
    "worker_cpu_share": ("share", "higher", "program_counter", "host",
                         "delivered_MBps", CELLS),
    "reader_cpu_share": ("share", "lower", "program_counter", "file input",
                         "delivered_MBps", CELLS),
    "enqueue_blocked_share": ("share", "lower", "program_counter",
                              "serialize / sink", "delivered_MBps", CELLS),
}
W, R, S = 501, 502, 503                 # worker, reader and sender thread ids


def _a(cpu_s, tid, **more):
    return dict(more, cpu_s=cpu_s, tid=tid)


def _thread(tid, cpu_s, runq, vol, invol=0, last_cpu=1, slices=10):
    return {"tid": tid, "cpu_s": cpu_s, "runq_wait_s": runq,
            "timeslices": slices, "voluntary_switches": vol,
            "involuntary_switches": invol, "last_cpu": last_cpu}


def _obs(with_source: bool) -> dict:
    """A traced window of 10 s whose slice of 2 s delivered 0.2 GB and whose
    scrapes lie 12 s apart; ``with_source`` False is the parent's program:
    the same spans without ``cpu_s`` and ``tid``, no ``input.file.round``, no
    ``threads`` section and no ``enqueue_blocked_seconds``."""
    spans = [
        # the worker: a group in flight (a stopwatch root), its two stages
        ["pipeline.process", 101.0, 0.9, 1, None, _a(None, W)],
        ["processor.p.dispatch", 101.0, 0.30, 2, 1, _a(0.20, W)],
        ["device.pack", 101.0005, 0.05, 3, 2, _a(0.04, W)],
        ["device.submit", 101.05, 0.10, 4, 2, _a(0.02, W)],
        ["device.roundtrip", 101.05, 0.6, 5, 1, _a(None, W)],
        ["processor.p.complete", 101.5, 0.40, 6, 1, _a(0.15, W)],
        ["device.wait", 101.5005, 0.10, 7, 6, _a(0.0, W)],
        ["device.d2h", 101.6, 0.05, 8, 6, _a(0.01, W)],
        ["runtime.gc", 101.7, 0.02, 9, 6, _a(0.02, W, generation=1)],
        # the reader's round
        ["input.file.round", 101.0, 0.50, 20, None, _a(0.20, R, reads=1)],
        ["input.file.discover", 101.0005, 0.05, 21, 20, _a(0.01, R)],
        ["input.file.read", 101.05, 0.30, 22, 20, _a(0.10, R)],
        ["input.file.push", 101.35, 0.05, 23, 20, _a(0.02, R)],
        ["input.file.checkpoint", 101.40, 0.02, 24, 20, _a(0.01, R)],
        # the sender: one native call, the pair's CPU on the first
        ["flusher.serialize", 102.0, 0.10, 30, None, _a(0.15, S)],
        ["flusher.write", 102.1, 0.08, 31, None, _a(None, S)],
    ]
    status0 = {"uptime_s": 50.0, "flush": {"p/f": {"batches_total": 10}}}
    status1 = {"uptime_s": 62.0, "flush": {"p/f": {"batches_total": 90}}}
    if with_source:
        status0["flush"]["p/f"]["enqueue_blocked_seconds"] = 0.5
        status1["flush"]["p/f"]["enqueue_blocked_seconds"] = 1.1
        status0["threads"] = {"at_s": 50.25, "by_name": {
            "processor-0": _thread(W, 40.0, 1.0, 1000),
            "file-server": _thread(R, 10.0, 0.5, 400),
            "MainThread": _thread(500, 1.0, 0.0, 10)},
            "other": {"threads": 40, "cpu_s": 20.0, "runq_wait_s": 2.0}}
        status1["threads"] = {"at_s": 62.25, "by_name": {
            "processor-0": _thread(W, 50.8, 1.12, 4000, last_cpu=3),
            "processor-dispatch": _thread(504, 0.3, 0.0, 50),
            "file-server": _thread(R, 13.6, 0.56, 1000),
            "f-sender": _thread(S, 2.4, 0.0, 600),
            "MainThread": _thread(500, 1.0, 0.0, 12)},
            "other": {"threads": 42, "cpu_s": 26.0, "runq_wait_s": 2.6}}
    else:
        spans = [s[:5] + [{k: v for k, v in s[5].items()
                           if k not in ("cpu_s", "tid")}]
                 for s in spans if not s[0].startswith("input.file.") or
                 s[0] == "input.file.read"]
        spans = [s[:4] + [None if s[0] == "input.file.read" else s[4], s[5]]
                 for s in spans]
    return {
        "t0": 100.0, "t1": 110.0, "line_bytes": 1000,
        "tail": {"t": np.array([0.0, 100.0, 101.0, 103.0, 110.0, 111.5]),
                 "last_seq": np.array([-1, -1, 99_999, 299_999, 999_999,
                                       1_199_999])},
        "slice": (101.0, 103.0), "spans": spans, "trace": None,
        "status0": status0, "status1": status1,
    }


def _read(name, obs):
    return spec.load_module("metrics", name).read(obs)


# -- the entries ----------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_is_found_by_name_with_its_cells_and_its_reader(name):
    (m,) = [m for m in BM["per_layer"] if m["name"] == name]
    unit, better, source, layer, moves, cells = ENTRIES[name]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": moves}
    assert set(cells) <= set(m["workloads"])
    assert layer in {o["layer"] for o in BM["per_layer"]
                     if o["name"] not in ENTRIES}      # a layer that is there
    assert os.path.exists(os.path.join(spec.BENCH_DIR, "metrics",
                                       name.split(".", 1)[0] + ".py"))
    for cell in cells:
        assert m in spec.metrics_of_cell(BM, cell, "per_layer")


def test_seven_entries_and_two_readers_waiting_for_a_kernel_that_counts():
    assert len(ENTRIES) == 7
    listed = {m["name"] for m in BM["per_layer"]}
    for name in UNLISTED:
        assert name not in listed
        assert os.path.exists(os.path.join(
            spec.BENCH_DIR, "metrics", name.split(".", 1)[0] + ".py"))
    for cell in SAT:                            # on the 512 KiB backlog
        assert spec.find_cell(BM, cell)["traffic"] == "backlog", cell


# -- the readers on a made-up window ----------------------------------------------------

def test_span_readers_give_the_work_inside_the_wall_time():
    obs = _obs(True)
    # processor.* self CPU: (0.20 − 0.04 − 0.02) + (0.15 − 0.0 − 0.01 − 0.02)
    assert _read("proc_stage_cpu_s_per_GB", obs) == pytest.approx(0.26 / 0.2)
    assert _read("device_copy_cpu_s_per_GB", obs) == pytest.approx(0.07 / 0.2)
    assert _read("reader_round_s_per_GB", obs) == pytest.approx(0.50 / 0.2)
    # the worker's accounted wall: the two stages (0.70 s, children inside
    # them); its CPU: 0.35; the stopwatch spans are on neither side
    assert _read("worker_offcpu_share", obs) == pytest.approx(1 - 0.35 / 0.70)
    # and never more work than wall
    wall = _read("proc_stage_s_per_GB", obs)
    assert _read("proc_stage_cpu_s_per_GB", obs) <= wall
    assert _read("device_copy_cpu_s_per_GB", obs) \
        <= _read("device_copy_s_per_GB", obs)


def test_counter_readers_give_shares_of_the_window():
    obs = _obs(True)
    # 10.8 CPU seconds between the scrapes, ten twelfths of the bytes in the
    # window's 10 s
    assert _read("worker_cpu_share", obs) == pytest.approx(10.8 / 1.2 / 10)
    assert _read("reader_cpu_share", obs) == pytest.approx(3.6 / 1.2 / 10)
    assert _read("worker_runq_share.sat", obs) == pytest.approx(0.12 / 12.0)
    assert _read("worker_runq_share.tail", obs) == pytest.approx(0.01)
    # 3,000 switches over the 1,200 MB settled from the window's start on
    assert _read("worker_switches_per_MB", obs) == pytest.approx(2.5)
    assert _read("enqueue_blocked_share", obs) == pytest.approx(0.6 / 1.2 / 10)
    assert _read("worker_cpu_share", obs) \
        + _read("worker_runq_share.sat", obs) <= 1.0


@pytest.mark.parametrize("name", COUNTER_SOURCED + UNLISTED[:2])
def test_an_idle_tail_between_the_scrapes_does_not_dilute_a_share(name):
    """A traced run's later scrape waits for the profiler's stop (minutes in
    a fused cell) while the agent idles: the counted seconds go with the
    bytes, so the share is the window's whatever the scrapes' distance."""
    obs, late = _obs(True), _obs(True)
    late["status1"]["threads"]["at_s"] += 150.0
    late["status1"]["uptime_s"] += 150.0
    assert _read(name, late) == pytest.approx(_read(name, obs))
    # and the drain's part of the bytes takes its part of the seconds
    short = _obs(True)
    short["tail"]["last_seq"][-1] = 999_999         # nothing after the window
    assert _read(name, short) == pytest.approx(1.2 * _read(name, obs))


@pytest.mark.parametrize("name", sorted(ENTRIES) + UNLISTED)
def test_reader_gives_nothing_never_zero_without_its_source(name):
    assert _read(name, _obs(False)) is None
    bare = _obs(False)
    bare.update(spans=None, slice=None, status0=None, status1=None)
    assert _read(name, bare) is None


@pytest.mark.parametrize("name", sorted(ENTRIES) + UNLISTED)
def test_reader_gives_a_float_with_its_source(name):
    assert isinstance(_read(name, _obs(True)), float)


@pytest.mark.parametrize("name", SPAN_SOURCED)
def test_span_readers_give_nothing_on_an_untraced_run(name):
    obs = _obs(True)
    obs.update(spans=None, slice=None)
    assert _read(name, obs) is None


@pytest.mark.parametrize("name", COUNTER_SOURCED + UNLISTED)
def test_counter_readers_need_no_trace(name):
    obs = _obs(True)
    obs.update(spans=None, slice=None)
    assert isinstance(_read(name, obs), float)


def test_a_quiet_sender_is_a_zero_and_a_missing_counter_is_nothing():
    obs = _obs(True)
    obs["status1"]["flush"]["p/f"]["enqueue_blocked_seconds"] = 0.5
    assert _read("enqueue_blocked_share", obs) == 0.0   # a reading, not None
    del obs["status1"]["flush"]["p/f"]["enqueue_blocked_seconds"]
    assert _read("enqueue_blocked_share", obs) is None


def test_fields_the_kernel_did_not_give_are_nothing():
    obs = _obs(True)
    for st in (obs["status0"], obs["status1"]):
        for row in st["threads"]["by_name"].values():
            del row["runq_wait_s"]
    assert _read("worker_runq_share.sat", obs) is None
    assert _read("worker_cpu_share", obs) == pytest.approx(0.9)


def test_the_busiest_worker_is_the_worker_and_a_new_thread_counts_from_zero():
    obs = _obs(True)
    dt, deltas, other = threads.by_name_delta(obs)
    assert dt == pytest.approx(12.0)
    assert threads.busiest(deltas, "processor-")[0] == "processor-0"
    assert deltas["processor-dispatch"]["cpu_s"] == pytest.approx(0.3)
    assert deltas["f-sender"]["voluntary_switches"] == 600
    assert deltas["processor-0"]["last_cpu"] == [1, 3]
    assert other["cpu_s"] == pytest.approx(6.0) and other["threads"] == [40, 42]
    # a name the later scrape has under another tid: a new thread
    obs["status1"]["threads"]["by_name"]["file-server"]["tid"] = 999
    assert threads.by_name_delta(obs)[1]["file-server"]["cpu_s"] \
        == pytest.approx(13.6)


def test_the_self_account_leaves_stopwatches_out_and_says_their_seconds():
    obs = _obs(True)
    by, left = threads.self_account(obs["spans"], W)
    assert left == {"pipeline.process": pytest.approx(0.9),
                    "device.roundtrip": pytest.approx(0.6)}
    assert by["processor.p.dispatch"] == [pytest.approx(0.15),
                                          pytest.approx(0.14)]
    assert "input.file.round" not in by and threads.worker_tid(obs) == W
    everything, _ = threads.self_account(obs["spans"])
    assert everything["input.file.round"] == [pytest.approx(0.08),
                                              pytest.approx(0.06)]


def test_the_rounds_makeup_has_the_cpu_beside_each_part(capsys):
    obs = _obs(True)
    doc = threads.makeup_cpu(obs, "input.file.round")
    assert doc["seconds"] == [pytest.approx(0.50), pytest.approx(0.20)]
    assert doc["self"] == [pytest.approx(0.08), pytest.approx(0.06)]
    assert doc["children"]["input.file.read"] == [pytest.approx(0.30),
                                                  pytest.approx(0.10)]
    _read("reader_round_s_per_GB", obs)
    _read("worker_cpu_share", obs)
    err = capsys.readouterr().err
    assert "input.file.round in the slice" in err
    assert "threads between the scrapes" in err and '"other"' in err


def test_no_span_names_cpu_passes_its_wall_but_the_native_pair():
    over = {n: row for n, row in threads.cpu_by_name(_obs(True)).items()
            if row[1] > row[0] * 1.01}
    # one native call timed as two spans: the pair's CPU is on the first
    assert list(over) == ["flusher.serialize"]


def test_idle_gaps_by_thread_give_each_thread_its_own_column():
    obs = _obs(True)
    ns = 1e9
    obs["trace"] = {"lo_ns": 0.0, "hi_ns": 2 * ns, "events": [
        ["/device:TPU:0", "XLA Ops", "%extract.1 = x", 0.6 * ns, 0.1 * ns]]}
    gaps = threads.idle_gaps_by_thread(obs)
    assert set(gaps) == {"processor-0", "file-server", "f-sender"}
    worker = dict(gaps["processor-0"])
    reader = dict(gaps["file-server"])
    # the device ran 0.1 s of the slice's 2 s (101.6–101.7, inside the
    # worker's d2h); each thread's column is the other 1.9 s by what THAT
    # thread was doing, and the stopwatches (the group's root, the round
    # trip) take none of it from the stages under or beside them
    near = dict(abs=2e-3)
    assert worker["device.pack"] == pytest.approx(0.05, **near)
    assert worker["device.submit"] == pytest.approx(0.10, **near)
    assert worker["processor.p.dispatch"] == pytest.approx(0.15, **near)
    assert worker["device.wait"] == pytest.approx(0.10, **near)
    assert "device.roundtrip" not in worker and "device.d2h" not in worker
    assert reader["input.file.read"] == pytest.approx(0.30, **near)
    assert reader["_no_span_"] == pytest.approx(1.4)
    for column in gaps.values():
        assert sum(v for _n, v in column) == pytest.approx(1.9)
    assert threads.idle_gaps_by_thread(_obs(False)) is None


# -- one traced rehearsal on the CPU ----------------------------------------------------

def test_traced_rehearsal_prints_the_new_metrics_and_the_threads_line():
    # the device path forced on the CPU (the routing probe would keep every
    # group on the host walker here): the dispatch legs then exist
    env = dict(os.environ, LOONG_NATIVE_T1="0", LOONG_DEVICE_MIN_BYTES="0")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", "regex512.backlog", "--seed", "2147483693",
         "--seconds", "2.5", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["correct"] is True, doc["checks"]
    got = doc["metrics"]
    for name in SPAN_SOURCED + COUNTER_SOURCED:
        assert isinstance(got[name]["value"], float), name
    assert 0.0 < got["proc_stage_cpu_s_per_GB"]["value"] \
        <= got["proc_stage_s_per_GB.sat"]["value"]
    assert got["device_copy_cpu_s_per_GB"]["value"] \
        <= got["device_copy_s_per_GB"]["value"]
    assert 0.0 <= got["worker_offcpu_share"]["value"] < 1.0
    assert 0.0 < got["worker_cpu_share"]["value"] <= 1.01
    assert 0.0 < got["reader_cpu_share"]["value"] <= 1.01
    for line in ("threads between the scrapes", "input.file.round in the "
                 "slice", "the worker's account by span"):
        assert line in r.stderr, line
