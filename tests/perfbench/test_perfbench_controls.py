"""The controls, as whole runs: the harness's look for a chip is skipped (the
CPU switch), the rest of a run is driven, and with the timed path broken
underneath ``correct`` has to come out false — once for each fault a cell of
this system can have.  The stream-level controls (every guarantee, on a
recorded sink) are in test_perfbench_units.py."""

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


SECONDS = {"regex512.burst40": "2"}          # a whole period at the least


def _run(workload, fault):
    seconds = SECONDS.get(workload, "1")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", workload, "--seed", "77", "--seconds", seconds,
         "--trace", "0", "--fault", fault],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stderr


@pytest.mark.parametrize("workload,fault,failing", [
    # half of a batch left out / an answer lost: a line never reaches the sink
    ("regex512.burst40", "drop_row", "rows_off_sequence"),
    # a token altered where it is produced
    ("filter512.backlog", "alter_field", "records_differ"),
    # the conservation ledger does not come back to 0
    ("regex512.backlog", "residual", "ledger_residual"),
])
def test_a_broken_path_comes_out_not_correct(workload, fault, failing):
    doc, stderr = _run(workload, fault)
    assert doc["correct"] is False
    assert doc["checks"][failing]["value"] > doc["checks"][failing]["limit"]
    assert f"check {failing}:" in stderr and "<-- FAILS" in stderr


def test_a_sink_that_stops_advancing_comes_out_not_correct(tmp_path,
                                                           monkeypatch):
    """A step that returns its state unchanged: the sink stops growing as far
    as the comparison can see, so lines written never settle."""
    traffic = spec.load_traffic("steady10")
    assert traffic["drain_limit_s"] >= 30        # the real limit is long ...
    # ... so this test drives the harness in-process with a short one, and
    # with the steady trickle in the burst cell's place (cheap on a CPU)
    import argparse
    import time
    from benchlib import harness
    short = dict(traffic, drain_limit_s=3, harness_cores=0)
    monkeypatch.setattr(spec, "load_traffic", lambda name: short)
    args = argparse.Namespace(
        workload="regex512.burst40", seed=78, seconds=1.0, trace=0,
        fault="stall")
    import contextlib
    import io
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.run_cell(args, time.monotonic(), work_dir=str(tmp_path))
    assert rc == 0
    doc = json.loads(out.getvalue().strip().splitlines()[-1])
    assert doc["correct"] is False
    assert doc["checks"]["unsettled_lines"]["value"] > 0
    assert doc["failed"] > 0
