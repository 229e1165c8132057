"""Rehearsal of every cell end to end at a tiny size on the CPU: the whole
harness (launcher, agent child, generator, tailer, comparison, metric
readers, result line) with the last line's keys checked.  The numbers are CPU
numbers and are not looked at: only that they are there, and that the
comparison passes on a sound run and fails on a broken one."""

import os
import sys

# the benchmark's library lives beside the benchmark, not in the program
sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench"))

import json
import shutil
import subprocess

import pytest

from benchlib import spec

REPO = spec.ROOT
BM = spec.load_benchmark()
SECONDS = {"regex512.burst40": "2"}          # a whole period at the least


def _run(workload, *extra, root=REPO, env=None, seconds=None, trace="0",
         seed="2147483659"):
    env = dict(os.environ if env is None else env)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"),
         "--workload", workload, "--seed", seed,
         "--seconds", seconds or SECONDS.get(workload, "1.5"),
         "--trace", trace, *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    return r


def _result(r):
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _check_result(doc, bm, workload, section):
    assert list(doc)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(doc)[-1] == "checks"           # the comparison comes last
    assert doc["correct"] is True, doc["checks"]
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(doc["device"])
    assert doc["device"]["platform"] == "cpu"   # said, never hidden
    declared = {m["name"]: m for m in
                spec.metrics_of_cell(bm, workload, section)}
    assert set(doc["metrics"]) <= set(declared)
    for name, m in doc["metrics"].items():
        assert m["unit"] == declared[name]["unit"]
        assert isinstance(m["value"], float)
    for name, c in doc["checks"].items():
        assert c["value"] <= c["limit"], name
    return doc


@pytest.mark.parametrize("workload", [w["name"] for w in BM["workloads"]])
def test_cell_end_to_end(workload):
    r = _run(workload)
    doc = _check_result(_result(r), BM, workload, "end_to_end")
    want = {m["name"] for m in
            spec.metrics_of_cell(BM, workload, "end_to_end")}
    assert set(doc["metrics"]) == want          # every end-to-end metric
    assert all(v["value"] > 0 for v in doc["metrics"].values())
    # the series and the comparison are on earlier lines, the limits on stderr
    assert "series delivered_MBps" in r.stdout
    assert "routing: device_row_share" in r.stdout      # every run says it
    assert r.stderr.strip().splitlines()[-1].startswith("check ")


def test_traced_cell_reports_layers_and_only_what_it_could_read():
    r = _run("regex512.burst40", trace="1")
    doc = _check_result(_result(r), BM, "regex512.burst40", "per_layer")
    # host-clock and counter readers found their numbers on the CPU too
    for name in ("gen_late_p99_ms", "e2f_p50_ms", "e2f_p99_ms",
                 "read_lag_KiB", "queue_wait_p50_ms.tail",
                 "device_row_share.tail", "sink_flush_KiB_p50"):
        assert name in doc["metrics"], name
    # no device operation ran, so nothing device-sourced is reported: a
    # reader that finds nothing returns nothing, never 0
    assert "busy_s" not in doc["device"] and "breakdown" not in doc
    assert "setup_s" not in doc["metrics"]


def test_no_accelerator_and_no_cpu_switch_fails_without_a_result():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    r = _run("regex512.backlog", env=env, seed="2147483660")
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "perfbench FAILED" in r.stderr


def test_alone_in_a_directory_fails_without_a_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: non-zero, no line."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in BM["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p)
    r = _run("regex512.backlog", root=str(tmp_path))
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "holds no loongcollector_tpu package" in r.stderr


def test_unknown_workload_fails():
    r = _run("nosuch.cell")
    assert r.returncode != 0 and '"correct"' not in r.stdout


@pytest.fixture
def copy_of_the_checkout(tmp_path):
    """The benchmark in a temporary checkout: its own files copied, the
    program linked in."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "perfbench"), tmp_path / "perfbench")
    for name in ("loongcollector_tpu", "native"):
        os.symlink(os.path.join(REPO, name), tmp_path / name)
    return tmp_path


def test_a_config_a_mix_a_cell_and_a_metric_are_added_as_files_alone(
        copy_of_the_checkout):
    """What a later PR does: new files and new entries, no edit of a file
    that is there."""
    root = copy_of_the_checkout
    bench = root / "perfbench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    # a configuration: its directory of files (here a copy with another pool)
    shutil.copytree(bench / "configs" / "file_regex_apache_512",
                    bench / "configs" / "file_regex_apache_512_pool64")
    cfg_path = bench / "configs" / "file_regex_apache_512_pool64" / "config.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["name"] = "file_regex_apache_512_pool64"
    cfg["source"]["pool"] = 100
    cfg_path.write_text(json.dumps(cfg))
    # a traffic mix: one data file for the general generator
    (bench / "traffic" / "steady2.json").write_text(json.dumps({
        "why": "a trickle", "mode": "open", "arrivals": "exponential",
        "rate_MBps": 2, "write_lines": 4,
        "warmup": {"backlog_MiB": 1, "schedule_s": 0.5, "idle_s": 0.1},
        "drain_limit_s": 60, "harness_cores": 0, "check_sample_share": 1.0,
        "ledger_poll_hz": 4}))
    # a per-layer metric: a small reader of its own
    (bench / "metrics" / "sink_reads_per_s.py").write_text(
        '"""serialize / sink: reads of the tailer that found new records, '
        'per second."""\n\n\n'
        "def read(obs):\n"
        "    t = obs['tail']['t']\n"
        "    n = ((t >= obs['t0']) & (t < obs['t1'])).sum()\n"
        "    return n / (obs['t1'] - obs['t0']) if n else None\n")
    # and the entries
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({
        "name": "file_regex_apache_512_pool64", "source": "a test",
        "file": "perfbench/configs/file_regex_apache_512_pool64/config.json",
        "reduced": [], "why": "a test"})
    bm["workloads"].append({
        "name": "regex512p64.steady2", "config": "file_regex_apache_512_pool64",
        "traffic": "steady2", "chips": 1, "why": "a test"})
    for m in bm["end_to_end"]:
        if m["name"] == "e2f_p95_ms":
            m["workloads"].append("regex512p64.steady2")
    bm["per_layer"].append({
        "name": "sink_reads_per_s", "unit": "1/s", "better": "lower",
        "source": "host_clock", "layer": "serialize / sink",
        "moves": "e2f_p95_ms", "workloads": ["regex512p64.steady2"]})
    # a quantity that has its reader already: an entry alone, under a suffix
    bm["per_layer"].append({
        "name": "sink_flush_KiB_p50.p64", "unit": "KiB", "better": "lower",
        "source": "host_clock", "layer": "serialize / sink",
        "moves": "e2f_p95_ms", "workloads": ["regex512p64.steady2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    e2e = _result(_run("regex512p64.steady2", root=str(root)))
    assert e2e["correct"] is True and set(e2e["metrics"]) \
        == {"e2f_p95_ms", "setup_s"}
    traced = _result(_run("regex512p64.steady2", root=str(root), trace="1"))
    assert traced["correct"] is True
    assert set(traced["metrics"]) == {"sink_reads_per_s",
                                      "sink_flush_KiB_p50.p64"}
    assert traced["metrics"]["sink_reads_per_s"]["value"] > 0
    # nothing that was there has changed
    assert all(p.read_bytes() == data for p, data in before.items())
