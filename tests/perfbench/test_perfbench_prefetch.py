"""Rehearsal of `d2h_prefetch_share` (perfbench/metrics/d2h_prefetch_share.py):
the window difference of the program's two counters on hand-made status pages,
nothing (never 0) from a program without the counter or a window without a
dispatch, its entry in BENCHMARK.json, and one traced run of each saturated
cell on the CPU in which every dispatch started its copy back at submit.  A
count, not a time: the CPU run says what is counted, never how fast."""

import os
import sys

# the benchmark's library lives beside the benchmark, not in the program
sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench"))

import json
import subprocess

import pytest

from benchlib import spec

REPO = spec.ROOT
BM = spec.load_benchmark()
NAME = "d2h_prefetch_share"
CELLS = ["regex512.backlog", "filter512.backlog"]


def _read(dev0, dev1):
    obs = {"status0": None if dev0 is None else {"device": dev0},
           "status1": None if dev1 is None else {"device": dev1}}
    return spec.load_module("metrics", NAME).read(obs)


def _dev(dispatched, prefetched=None):
    dev = {"dispatched_total": dispatched, "inflight_bytes": 0}
    if prefetched is not None:
        dev["d2h_prefetched_total"] = prefetched
    return dev


@pytest.mark.parametrize("dev0,dev1,want", [
    # the parent's status page: dispatches, no such counter
    pytest.param(_dev(100), _dev(9100), None, id="no_counter"),
    pytest.param(None, None, None, id="no_status_page"),
    pytest.param({}, {}, None, id="plane_never_built"),
    # the counter is there but nothing was dispatched between the scrapes
    pytest.param(_dev(500, 500), _dev(500, 500), None, id="empty_window"),
    # the window's difference, not the lifetime's ratio
    pytest.param(_dev(1000, 0), _dev(9000, 8000), 1.0, id="all_in_window"),
    pytest.param(_dev(1000, 1000), _dev(9000, 7000), 0.75, id="three_quarters"),
    pytest.param(_dev(1000, 1000), _dev(9000, 1000), 0.0, id="none_in_window"),
    # a dispatch between its two counts at the first scrape is still one
    pytest.param(_dev(1000, 999), _dev(2000, 2000), 1.0, id="one_in_between"),
    # the plane came up inside the window
    pytest.param({}, _dev(40, 30), 0.75, id="first_scrape_before_the_plane"),
])
def test_share_is_the_window_difference_or_nothing(dev0, dev1, want):
    got = _read(dev0, dev1)
    if want is None:
        assert got is None          # None, never 0: the line leaves it out
    else:
        assert got == pytest.approx(want, rel=1e-12)


def test_the_entry_is_the_one_the_reader_expects():
    # by name, wherever later PRs put it; its cells a superset of this PR's
    (entry,) = [m for m in BM["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "share", "better": "higher",
        "source": "program_counter", "layer": "dispatch",
        "moves": "delivered_MBps"}
    assert set(CELLS) <= set(entry["workloads"])
    for cell in CELLS:
        assert NAME in {m["name"] for m in
                        spec.metrics_of_cell(BM, cell, "per_layer")}
    # a fact of the cell, not a pin: the burst reports no delivered_MBps,
    # the end-to-end metric this one moves
    assert NAME not in {m["name"] for m in spec.metrics_of_cell(
        BM, "regex512.burst40", "per_layer")}


@pytest.mark.parametrize("workload", CELLS)
def test_traced_saturated_cell_starts_every_copy_at_submit(workload):
    # the device path forced on the CPU (the routing probe would keep every
    # group on the host walker here), as test_perfbench_spans.py does
    env = dict(os.environ, LOONG_NATIVE_T1="0", LOONG_DEVICE_MIN_BYTES="0")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", workload, "--seed", "2147483671", "--seconds", "2.5",
         "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["correct"] is True, doc["checks"]
    assert doc["metrics"][NAME] == {"value": 1.0, "unit": "share"}
    # the legs it moves are still read from the same spans
    for name in ("device_copy_s_per_GB", "device_wait_s_per_GB"):
        assert isinstance(doc["metrics"][name]["value"], float)
