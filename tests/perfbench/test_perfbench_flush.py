"""Rehearsal of `flush_offload_share` (perfbench/metrics/flush_offload_share.py):
the window difference of the sender's two batch counters on hand-made
/debug/status pages, nothing (never 0) from a program without the `flush`
section or a window without a batch, its entry in BENCHMARK.json found by
name, and one traced run of the claimed cell on the CPU in which the sink's
batches are written by its sender thread while the three span metrics of the
layer still find their spans.  A count, not a time: the CPU run says what is
counted, never how fast."""

import os
import sys

# the benchmark's library lives beside the benchmark, not in the program
sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench"))

import json
import subprocess

import pytest

import bm_entries
from benchlib import spec

REPO = spec.ROOT
BM = spec.load_benchmark()
NAME = "flush_offload_share"
CELLS = ["regex512.backlog", "filter512.backlog", "json1k_filter.backlog",
         "grok_nginx.backlog", "http_classify.backlog"]
SINK = "bench/flusher_file/0"


def _status(**sinks):
    """A /debug/status page with the `flush` section the program's
    FlushSender.status() writes: (batches_total, offloaded_total) per sink."""
    return {"uptime_s": 1.0, "flush": {
        sink.replace("__", "/"): {
            "batches_total": batches, "offloaded_total": offloaded,
            "enqueue_blocked_total": 0, "enqueue_blocked_seconds": 0.0,
            "depth": batches - offloaded, "depth_max": 2}
        for sink, (batches, offloaded) in sinks.items()}}


ONE = SINK.replace("/", "__")
TWO = "other__flusher_file__1"


@pytest.mark.parametrize("status0,status1,want", [
    # the parent's page: no such section
    pytest.param({"uptime_s": 1.0, "file_input": {"reads_total": 5}},
                 {"uptime_s": 2.0, "file_input": {"reads_total": 9000}},
                 None, id="no_flush_section"),
    pytest.param(None, None, None, id="no_status_page"),
    # the section is there but no batch left the batcher between the scrapes
    pytest.param(_status(**{ONE: (40, 40)}), _status(**{ONE: (40, 40)}),
                 None, id="no_batch_in_window"),
    # the window's difference, not the lifetime's ratio
    pytest.param(_status(**{ONE: (100, 100)}),
                 _status(**{ONE: (8700, 8700)}), 1.0, id="all_in_window"),
    pytest.param(_status(**{ONE: (1000, 1000)}),
                 _status(**{ONE: (9000, 7000)}), 0.75, id="three_quarters"),
    pytest.param(_status(**{ONE: (1000, 1000)}),
                 _status(**{ONE: (9000, 1000)}), 0.0, id="none_in_window"),
    # batches that waited at the first scrape land inside the window
    pytest.param(_status(**{ONE: (1000, 996)}),
                 _status(**{ONE: (2000, 2000)}), 1.0, id="waiting_at_first"),
    # ... and those that wait at the second have not landed yet
    pytest.param(_status(**{ONE: (1000, 1000)}),
                 _status(**{ONE: (2000, 1994)}), 0.994,
                 id="waiting_at_second"),
    # the sink's first batch came inside the window
    pytest.param({"uptime_s": 1.0}, _status(**{ONE: (40, 30)}), 0.75,
                 id="first_scrape_before_the_section"),
    # two sinks are read together
    pytest.param(_status(**{ONE: (0, 0)}),
                 _status(**{ONE: (3000, 3000), TWO: (1000, 600)}), 0.9,
                 id="two_sinks"),
])
def test_share_is_the_window_difference_or_nothing(status0, status1, want):
    got = spec.load_module("metrics", NAME).read(
        {"status0": status0, "status1": status1})
    if want is None:
        assert got is None          # None, never 0: the line leaves it out
    else:
        assert got == pytest.approx(want, rel=1e-12)


def test_the_entry_is_found_by_name_and_is_the_one_the_reader_expects():
    (entry,) = [m for m in BM["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "share", "better": "higher",
        "source": "program_counter", "layer": "serialize / sink",
        "moves": "delivered_MBps"}
    assert set(CELLS) <= set(entry["workloads"])
    assert os.path.isfile(os.path.join(REPO, "perfbench", "metrics",
                                       NAME + ".py"))
    # the layer is one the benchmark already names, letter for letter
    assert "serialize / sink" in {m["layer"] for m in BM["per_layer"]
                                  if m["name"] != NAME}
    # every cell that lists it has the reader's source: a file sink, whose
    # sender thread counts the batches it wrote
    for cell in entry["workloads"]:
        assert "flusher_file" in bm_entries.plugins(BM, cell), cell


def test_traced_regex_cell_writes_its_batches_on_the_sender():
    # the device path forced on the CPU (the routing probe would keep every
    # group on the host walker here), as test_perfbench_spans.py does
    env = dict(os.environ, LOONG_NATIVE_T1="0", LOONG_DEVICE_MIN_BYTES="0")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", CELLS[0], "--seed", "2147483711", "--seconds", "2.5",
         "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    # the ledger at rest with residual 0 and send_ok equal to the sink's rows
    assert doc["correct"] is True, doc["checks"]
    # every batch between the scrapes but the few that wait at the second
    # (a 2.5 s window on the CPU holds a few dozen; the chip's 45 s, 8,000)
    share = doc["metrics"][NAME]
    assert share["unit"] == "share" and 0.8 <= share["value"] <= 1.0
    # the layer's span metrics still find their spans: the worker's hand-over
    # under flusher.send, the two halves of the flush on the sender thread
    for name in ("flush_stage_s_per_GB", "serialize_s_per_GB",
                 "sink_write_s_per_GB"):
        assert isinstance(doc["metrics"][name]["value"], float), name
        assert doc["metrics"][name]["value"] > 0, name
