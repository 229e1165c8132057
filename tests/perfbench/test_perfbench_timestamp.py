"""Rehearsal of `ts_column_row_share` (perfbench/metrics/ts_column_row_share.py):
the window difference of the timestamp processor's two row counters on
recorded /debug/status pages, nothing (never 0) from a program without the
label or a window without rows, its entry in BENCHMARK.json found by name,
and one traced run of its cell on the CPU in which every row of the time
column is parsed on the column path.  A count, not a time: the CPU run says
what is counted, never how fast."""

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
NAME = "ts_column_row_share"
CELL = "regex512.backlog"
LABEL = "processor_parse_timestamp_native/bench"


def _status(**labels):
    """A /debug/status page with the `parse` section the program's
    parse_telemetry.status() writes: (rows, fallback_rows) per label."""
    return {"uptime_s": 1.0, "parse": {
        label.replace("__", "/"): {"rows": rows, "fallback_rows": fallback,
                                   "drift_rows": 0, "degraded": False}
        for label, (rows, fallback) in labels.items()}}


TS = LABEL.replace("/", "__")
OTHER = "processor_parse_json_tpu__bench"


@pytest.mark.parametrize("status0,status1,want", [
    # the parent's page: the section is there for the JSON parser, the
    # timestamp processor reports nothing
    pytest.param(_status(**{OTHER: (100, 10)}), _status(**{OTHER: (900, 90)}),
                 None, id="no_label"),
    pytest.param({"uptime_s": 1.0}, {"uptime_s": 2.0}, None,
                 id="no_parse_section"),
    pytest.param(None, None, None, id="no_status_page"),
    # the label is there but no group took the column path between the scrapes
    pytest.param(_status(**{TS: (4096, 3)}), _status(**{TS: (4096, 3)}), None,
                 id="no_rows_in_window"),
    # the window's difference, not the lifetime's ratio
    pytest.param(_status(**{TS: (1024, 1024)}),
                 _status(**{TS: (1024 + 2048000, 1024)}), 1.0,
                 id="all_proven_in_window"),
    pytest.param(_status(**{TS: (1000, 0)}), _status(**{TS: (9000, 2000)}),
                 0.75, id="three_quarters"),
    pytest.param(_status(**{TS: (1000, 0)}), _status(**{TS: (9000, 8000)}),
                 0.0, id="none_proven"),
    # the first group came inside the window
    pytest.param(_status(), _status(**{TS: (2048, 512)}), 0.75,
                 id="first_scrape_before_the_label"),
    # another processor's rows on the same page are not this one's; two
    # pipelines with the processor are read together
    pytest.param(_status(**{OTHER: (500, 400), TS: (0, 0)}),
                 _status(**{OTHER: (5000, 4000), TS: (3000, 0),
                            TS + "_b": (1000, 400)}), 0.9,
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


def test_traced_regex_cell_parses_every_stamp_on_the_column_path():
    # the device path forced on the CPU (the routing probe would keep every
    # group on the host walker here), as test_perfbench_spans.py does
    env = dict(os.environ, LOONG_NATIVE_T1="0", LOONG_DEVICE_MIN_BYTES="0")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "2147483693", "--seconds", "2.5",
         "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    # `correct` compares __time__ of the sampled records with strptime's
    assert doc["correct"] is True, doc["checks"]
    assert doc["checks"]["times_differ"] == {"value": 0, "limit": 0}
    # rejected lines have no `time` and are not present: every present row
    # is 26 proven bytes
    assert doc["metrics"][NAME] == {"value": 1.0, "unit": "share"}
    assert isinstance(doc["metrics"]["proc_stage_s_per_GB.sat"]["value"],
                      float)
