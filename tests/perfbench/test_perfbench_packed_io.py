"""Rehearsal of `io_arrays_per_dispatch` (perfbench/metrics/io_arrays_per_dispatch.py):
the window difference of the program's two array counters over its dispatches
on hand-made /debug/status pages, nothing (never 0) from a program without the
counters or a window without a dispatch, its entry in BENCHMARK.json found by
name, and one traced run of the claimed cell and of the two-stage cell on the
CPU in which every dispatch crossed once each way while the dispatch layer's
other metrics still find what they read.  A count, not a time: the CPU run
says what is counted, never how fast."""

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
NAME = "io_arrays_per_dispatch"
CELLS = ["regex512.backlog", "filter512.backlog", "json1k_filter.backlog",
         "multiline_java.backlog", "grok_nginx.backlog",
         "http_classify.backlog"]


def _read(dev0, dev1):
    obs = {"status0": None if dev0 is None else {"device": dev0},
           "status1": None if dev1 is None else {"device": dev1}}
    return spec.load_module("metrics", NAME).read(obs)


def _dev(dispatched, h2d=None, d2h=None, counted=None):
    dev = {"dispatched_total": dispatched, "d2h_prefetched_total": dispatched}
    if h2d is not None:
        dev["h2d_arrays_total"] = h2d
        dev["d2h_arrays_total"] = d2h
    if counted is not None:
        # the dispatches whose arrays the two counts hold, counted with them
        dev["arrays_dispatched_total"] = counted
    return dev


@pytest.mark.parametrize("dev0,dev1,want", [
    # the parent's status page: dispatches, no such counters
    pytest.param(_dev(100), _dev(9100), None, id="no_counters"),
    pytest.param(None, None, None, id="no_status_page"),
    pytest.param({}, {}, None, id="plane_never_built"),
    # the counters are there but nothing was dispatched between the scrapes
    pytest.param(_dev(500, 500, 500), _dev(500, 500, 500), None,
                 id="empty_window"),
    # one array in and one out: the packed dispatch
    pytest.param(_dev(1000, 1000, 1000), _dev(9000, 9000, 9000), 2.0,
                 id="packed"),
    # what the parent's dispatches would read: (rows, lengths) in, the
    # extract's three outputs / the json program's seven back
    pytest.param(_dev(1000, 2000, 3000), _dev(9000, 18000, 27000), 5.0,
                 id="extract_tuple"),
    pytest.param(_dev(0, 0, 0), _dev(8000, 16000, 56000), 9.0,
                 id="json_keep_tuple"),
    # the multiline cell's two dispatches a group, classify and extract
    pytest.param(_dev(0, 0, 0), _dev(8000, 16000, 16000), 4.0,
                 id="classify_and_extract_tuple"),
    # the window's difference, not the lifetime's ratio: the warm-up's
    # tuple dispatches before the first scrape do not count
    pytest.param(_dev(1000, 2000, 3000), _dev(5000, 6000, 7000), 2.0,
                 id="window_not_lifetime"),
    # the plane came up inside the window
    pytest.param({}, _dev(40, 40, 40), 2.0,
                 id="first_scrape_before_the_plane"),
    # a faulted dispatch hands nothing over and starts no copy
    pytest.param(_dev(0, 0, 0), _dev(1000, 999, 999), 1.998,
                 id="one_faulted_dispatch"),
    # a dispatch admitted at the first scrape whose call had not returned:
    # counted as dispatched before it, its arrays after it ...
    pytest.param(_dev(1001, 1000, 1000), _dev(9000, 9000, 9000), 16000 / 7999,
                 id="one_in_between_by_admission"),
    # ... and none in between where the program counts the dispatches with
    # their arrays
    pytest.param(_dev(1001, 1000, 1000, 1000), _dev(9000, 9000, 9000, 9000),
                 2.0, id="one_in_between_counted_with_its_arrays"),
    pytest.param(_dev(1001, 1000, 1000, 1000), _dev(1001, 1000, 1000, 1000),
                 None, id="counted_empty_window"),
    pytest.param({}, _dev(41, 80, 80, 40), 4.0,
                 id="counted_first_scrape_before_the_plane"),
])
def test_arrays_per_dispatch_is_the_window_difference_or_nothing(
        dev0, dev1, want):
    got = _read(dev0, dev1)
    if want is None:
        assert got is None          # None, never 0: the line leaves it out
    else:
        assert got == pytest.approx(want, rel=1e-12)


def test_the_entry_is_found_by_name_and_is_the_one_the_reader_expects():
    (entry,) = [m for m in BM["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "arrays", "better": "lower",
        "source": "program_counter", "layer": "dispatch",
        "moves": "delivered_MBps"}
    assert set(CELLS) <= set(entry["workloads"])
    assert os.path.isfile(os.path.join(REPO, "perfbench", "metrics",
                                       NAME + ".py"))
    # the layer is one the benchmark already names, letter for letter
    assert "dispatch" in {m["layer"] for m in BM["per_layer"]
                          if m["name"] != NAME}
    # every cell that lists it has the reader's source, the device plane's
    # counters on /debug/status device: d2h_prefetch_share reads the same
    for cell in entry["workloads"]:
        names = {m["name"] for m in spec.metrics_of_cell(BM, cell, "per_layer")}
        assert "d2h_prefetch_share" in names, cell


@pytest.mark.parametrize("workload", ["regex512.backlog",
                                      "multiline_java.backlog"])
def test_traced_cell_crosses_once_each_way_per_dispatch(workload):
    # the device path forced on the CPU (the routing probe would keep every
    # group on the host walker here), as test_perfbench_spans.py does
    env = dict(os.environ, LOONG_NATIVE_T1="0", LOONG_DEVICE_MIN_BYTES="0")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", workload, "--seed", "2147483723", "--seconds", "2.5",
         "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["correct"] is True, doc["checks"]
    # the extract's dispatches, and in the multiline cell the classify's
    # too, each hand over one array and start one copy back
    assert doc["metrics"][NAME] == {"value": 2.0, "unit": "arrays"}
    # what else reads the dispatch still finds it: the copy back started at
    # submit for every dispatch, the copy and wait legs' spans, and a
    # geometry per pack (B x L of the rows, not of the packed buffer)
    assert doc["metrics"]["d2h_prefetch_share"]["value"] == 1.0
    for name in ("device_copy_s_per_GB", "device_wait_s_per_GB",
                 "dispatch_rt_p50_ms", "pad_row_share"):
        assert isinstance(doc["metrics"][name]["value"], float), name
    assert doc["metrics"]["device_copy_s_per_GB"]["value"] > 0
