"""Rehearsal of `ts_native_row_share` (perfbench/metrics/ts_native_row_share.py):
the window difference of `native_rows` over `rows` on recorded /debug/status
pages, nothing (never 0) from a program whose section has no such field (the
parent), from a window without rows and from an observation without pages, its
entry in BENCHMARK.json found by name, and one traced run of its cell on the
CPU in which every present row of the time column is stored by the one native
call a group.  A count, not a time: the CPU run says what is counted, never
how fast."""

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
NAME = "ts_native_row_share"
CELL = "regex512.backlog"
TS = "processor_parse_timestamp_native/bench"
OTHER = "processor_parse_json_tpu/bench"


def _status(**labels):
    """A /debug/status page with the `parse` section the program's
    parse_telemetry.status() writes; a label's value is (rows, fallback
    rows) as the parent writes it, or (rows, fallback rows, native rows,
    native calls)."""
    parse = {}
    for label, counts in labels.items():
        doc = {"rows": counts[0], "fallback_rows": counts[1],
               "drift_rows": 0, "degraded": False}
        if len(counts) == 4:
            doc["native_rows"], doc["native_calls"] = counts[2:]
        parse[label] = doc
    return {"uptime_s": 1.0, "parse": parse}


@pytest.mark.parametrize("status0,status1,want", [
    # the parent's section: the label, and no such field in it
    pytest.param(_status(**{TS: (1024, 0)}), _status(**{TS: (902144, 10)}),
                 None, id="parent_section"),
    # a process without the native library writes the parent's section
    pytest.param(_status(), _status(**{TS: (4096, 0), OTHER: (10, 1)}),
                 None, id="numpy_path"),
    pytest.param(_status(**{OTHER: (100, 10)}), _status(**{OTHER: (900, 90)}),
                 None, id="no_label"),
    pytest.param({"uptime_s": 1.0}, {"uptime_s": 2.0}, None,
                 id="no_parse_section"),
    pytest.param(None, None, None, id="no_status_page"),
    pytest.param({}, {}, None, id="empty_observation"),
    pytest.param(_status(**{TS: (4096, 3, 4093, 5)}),
                 _status(**{TS: (4096, 3, 4093, 5)}), None,
                 id="no_rows_in_window"),
    # the window's difference, not the lifetime's ratio
    pytest.param(_status(**{TS: (1024, 1024, 0, 2)}),
                 _status(**{TS: (1024 + 2048000, 1024, 2048000, 2002)}), 1.0,
                 id="all_stored_in_window"),
    pytest.param(_status(**{TS: (1000, 0, 1000, 1)}),
                 _status(**{TS: (9000, 2000, 7000, 9)}), 0.75,
                 id="three_quarters"),
    pytest.param(_status(**{TS: (1000, 0, 1000, 1)}),
                 _status(**{TS: (9000, 8000, 1000, 9)}), 0.0,
                 id="none_stored"),
    # the first group came inside the window
    pytest.param(_status(), _status(**{TS: (2048, 512, 1536, 3)}), 0.75,
                 id="first_scrape_before_the_label"),
    # another processor's rows are not this one's; two pipelines with the
    # processor are read together
    pytest.param(_status(**{OTHER: (500, 400), TS: (0, 0, 0, 1)}),
                 _status(**{OTHER: (5000, 4000), TS: (3000, 0, 3000, 4),
                            TS + "_b": (1000, 400, 600, 2)}), 0.9,
                 id="only_its_own_labels"),
])
def test_share_is_the_window_difference_or_nothing(status0, status1, want):
    got = spec.load_module("metrics", NAME).read(
        {"status0": status0, "status1": status1})
    if want is None:
        assert got is None          # None, never 0: the line leaves it out
    else:
        assert got == pytest.approx(want, rel=1e-12)


def test_the_entry_is_found_by_name_and_is_the_one_the_reader_expects():
    # by name, wherever later PRs put it: not by its place in `per_layer`
    (entry,) = [m for m in BM["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "share", "better": "higher",
        "source": "program_counter", "layer": "processors",
        "moves": "delivered_MBps"}
    assert CELL in entry["workloads"]
    assert os.path.isfile(os.path.join(REPO, "perfbench", "metrics",
                                       NAME + ".py"))
    # every cell that lists it has the reader's source: the timestamp
    # processor, whose rows the counters are
    for cell in entry["workloads"]:
        assert "processor_parse_timestamp_native" \
            in bm_entries.plugins(BM, cell), cell


def test_program_writes_the_fields_the_reader_reads():
    from loongcollector_tpu import native
    from loongcollector_tpu.processor import parse_telemetry
    parse_telemetry.reset_for_testing()
    try:
        parse_telemetry.note_rows("processor_parse_timestamp_native", "bench",
                                  1024, 0)
        before = {"parse": parse_telemetry.status()}
        assert "native_rows" not in before["parse"][TS]
        parse_telemetry.note_rows("processor_parse_timestamp_native", "bench",
                                  1024, 24, native_rows=1000, native_calls=2)
        after = {"parse": parse_telemetry.status()}
        assert after["parse"][TS]["native_calls"] == 2
    finally:
        parse_telemetry.reset_for_testing()
    read = spec.load_module("metrics", NAME).read
    assert read({"status0": before, "status1": after}) \
        == pytest.approx(1000 / 1024)
    assert read({"status0": None, "status1": before}) is None
    assert native.get_lib() is None or hasattr(native.get_lib(),
                                               "lct_timestamp_column")


def test_traced_regex_cell_stores_every_stamp_in_the_native_call():
    from loongcollector_tpu import native
    if native.get_lib() is None:
        pytest.skip("no native library in this process")
    # the device path forced on the CPU (the routing probe would keep every
    # group on the host walker here), as test_perfbench_timestamp.py does
    env = dict(os.environ, LOONG_NATIVE_T1="0", LOONG_DEVICE_MIN_BYTES="0")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "2147483777", "--seconds", "2.5",
         "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    # `correct` compares __time__ of the sampled records with strptime's
    assert doc["correct"] is True, doc["checks"]
    assert doc["checks"]["times_differ"] == {"value": 0, "limit": 0}
    # rejected lines have no `time` and are not present: every present row
    # is 26 proven bytes, stored by the call
    assert doc["metrics"][NAME] == {"value": 1.0, "unit": "share"}
    assert doc["metrics"]["ts_column_row_share"] == {"value": 1.0,
                                                     "unit": "share"}
