"""The multiline Java configuration's benchmark files (PR 31): the record
source is a pure function of (seed, j) at a fixed width with its sequence
number in the same columns of every record, its template classes are what
``config.json`` states, the plain reference agrees with ``re`` applied by
hand, a CPU rehearsal of the cell is ``correct`` and a broken path is not,
and the four readers give a number where the program has their source and
nothing (never 0) where it has not."""

import os
import sys

# the benchmark's library lives beside the benchmark, not in the program
sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench"))

import json
import re
import subprocess

import numpy as np
import pytest

import bm_entries
from benchlib import check, spec

BM = spec.load_benchmark()
CFG = spec.load_config(BM, "file_multiline_java_2k")
SEED = 2147483659
CELL = "multiline_java.backlog"
START = re.compile(rb"\d{4}-\d{2}-\d{2} .*")
PARSE = re.compile(rb"(\d{4}-\d{2}-\d{2} \d{2}:\d{2}:\d{2}) (\w+) ([\s\S]*)")


@pytest.fixture(scope="module")
def source():
    return spec.load_module("sources", "java_multiline_templates").make(
        CFG["source"], SEED)


@pytest.fixture(scope="module")
def reference():
    return spec.load_module("references", "multiline_regex").make(
        CFG["reference"])


def _templates(source):
    return [source.templates[k].tobytes() for k in range(source.pool)]


def test_source_is_a_pure_function_of_seed_and_record(source):
    mod = spec.load_module("sources", "java_multiline_templates")
    again = mod.make(CFG["source"], SEED)
    other = mod.make(CFG["source"], SEED + 1)
    assert np.array_equal(source.templates, again.templates)
    assert not np.array_equal(source.templates, other.templates)
    block = source.block(1000, 100)
    assert np.array_equal(block, again.block_at(np.arange(1000, 1100)))
    assert block.tobytes() == b"".join(source.line(j)
                                       for j in range(1000, 1100))
    assert np.array_equal(source.template_of(1000, 300),
                          again.template_of(1000, 300))
    # a seed beyond 32 signed bits, as the driver's are
    assert mod.make(CFG["source"], 2**31 + 12345).line(7)


def test_every_record_fills_the_width_and_its_sequence_columns(source):
    assert source.line_bytes == 2048 and source.templates.shape == (4096, 2048)
    at = source.seq_offset
    for rec in _templates(source):
        assert len(rec) == 2048 and rec.endswith(b"\n") and rec.isascii()
        assert rec[at - 4:at] == b"req=" and rec[at:at + 12] == b"0" * 12
        lines = rec[:-1].split(b"\n")
        assert 15 <= len(lines) <= 30 and max(map(len, lines)) < 256
        # one unit is one record: the head opens it and nothing else does
        assert START.fullmatch(lines[0])
        assert not any(START.fullmatch(ln) for ln in lines[1:])
        frames = [ln for ln in lines if ln.startswith(b"\tat ")]
        assert all(39 <= len(f) <= 255 for f in frames)
        assert all(39 <= len(f) < 140 for f in frames[:-1])
    big = source.line(999_999_999_999)
    assert len(big) == 2048 and b"req=999999999999 " in big


def test_class_counts_are_what_the_configuration_states(source):
    p = CFG["source"]
    kinds = source.kinds
    rejects = [k["reject"] for k in kinds if "reject" in k]
    assert len(kinds) == p["pool"] == 4096
    assert len(rejects) == round(4096 * p["reject_share"]) == 41
    assert {rejects.count(k) for k in set(rejects)} <= {20, 21} \
        and set(rejects) == {"seconds", "level"}
    parsable = [k for k in kinds if "reject" not in k]
    levels = [k["level"] for k in parsable]
    assert abs(levels.count("ERROR") / 4096 - 0.90) < 0.002
    assert abs(levels.count("WARN") / 4096 - 0.09) < 0.002
    assert sum("cause" in k for k in parsable) == round(len(parsable) * 0.6)
    assert sum("blank" in k for k in parsable) == round(len(parsable) * 0.01)
    recs = _templates(source)
    assert sum(b"\n\n" in r for r in recs) == round(len(parsable) * 0.01)
    with_cause = [r for r in recs if b"\nCaused by: " in r]
    assert abs(len(with_cause) / 4096 - 0.6) < 0.01
    assert all(re.search(rb"\n\t\.\.\. \d+ more\n$", r) for r in with_cause)
    for key in ("unit", "width", "head_line", "frames", "cause_share",
                "level_mix", "reject_share", "blank_share", "ascii", "pool",
                "not_in_the_cell", "cpu_usage_limit", "sink"):
        assert key in CFG["assumed"], key


def test_reference_agrees_with_re_applied_by_hand(source, reference):
    n_raw = 0
    for k, rec in enumerate(_templates(source)):
        unit = rec[:-1]
        got, epoch = reference.expected(unit)
        assert epoch is None
        m = PARSE.fullmatch(unit)
        if "reject" in source.kinds[k]:
            assert m is None and got == {"rawLog": unit.decode("latin-1")}
            n_raw += 1
            continue
        assert m is not None and list(got) == ["time", "level", "message"]
        assert got["time"].encode() == unit[:19]
        assert got["level"] == source.kinds[k]["level"]
        assert got["message"].encode() == unit[20 + len(got["level"]) + 1:]
        assert got["message"].count("\n") == unit.count(b"\n")
    assert n_raw == 41
    # every unit is kept: one sink record a unit
    assert check.keep_mask(source, reference).all()
    # a unit that would change its neighbour's record is refused, not answered
    with pytest.raises(ValueError):
        reference.expected(b"\tat x.Y.z(Y.java:1)\n\tat q")
    with pytest.raises(ValueError):
        reference.expected(b"2026-03-01 10:00:00 ERROR a\n2026-03-01 x")


def test_seqs_in_finds_every_records_unit(source, reference):
    seqs = [5, 6, 7, 123456789012, 9]
    recs = [json.dumps(dict(reference.expected(
        source.block_at(np.array([j]))[0].tobytes()[:-1])[0],
        __time__=1700000000)) for j in seqs]
    sink = ("\n".join(recs) + "\n").encode()
    assert source.seqs_in(sink).tolist() == seqs
    assert source.seqs_in(b"").size == 0


def test_the_cell_and_its_metrics_are_entries_alone():
    cell = spec.find_cell(BM, CELL)
    assert cell == dict(cell, config="file_multiline_java_2k",
                        traffic="backlog", chips=1)
    e2e = {m["name"] for m in spec.metrics_of_cell(BM, CELL, "end_to_end")}
    assert {"delivered_MBps", "setup_s"} <= e2e
    mine = {m["name"] for m in spec.metrics_of_cell(BM, CELL, "per_layer")}
    # its own entries and what every other saturated cell reports, but
    # gen_lead_min_MiB, whose reader multiplies the ledger's ingest events
    # (physical lines here) by line_bytes, and flush_offload_share, which
    # does not list the cell yet (PERF.md, Open questions)
    common = bm_entries.common_of_the_other_backlog_cells(BM, CELL)
    assert set(READERS) | (common - {"gen_lead_min_MiB",
                                     "flush_offload_share"}) <= mine
    assert CFG["reduced"] == [] and len(CFG["guarantees"]) == 7
    assert "needs_of_program" not in CFG["source"]
    entry = [c for c in BM["configs"] if c["name"] == CFG["name"]][0]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200 \
        and len(cell["why"]) <= 200
    # the pipeline is the quick start's, with the run's paths and a file sink
    quick = open(os.path.join(spec.ROOT, "example_config", "quick_start",
                              "multiline_java.yaml")).read()
    mine_yaml = open(os.path.join(CFG["dir"], CFG["pipeline"])).read()
    for text in ("StartPattern: '\\d{4}-\\d{2}-\\d{2} .*'",
                 "Regex: '(\\d{4}-\\d{2}-\\d{2} \\d{2}:\\d{2}:\\d{2}) (\\w+) "
                 "([\\s\\S]*)'", "Keys: [time, level, message]",
                 "Type: processor_parse_regex_tpu"):
        assert text in quick and text in mine_yaml, text


# -- whole runs on the CPU ------------------------------------------------------------

def _run(fault, seed="79"):
    r = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", seed, "--seconds", "1", "--trace", "0",
         "--fault", fault],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stderr


def test_a_cpu_rehearsal_of_the_cell_is_correct():
    doc, stderr = _run("none")
    assert doc["correct"] is True, stderr[-3000:]
    assert doc["failed"] == 0 and doc["attempted"] > 0
    assert set(doc["metrics"]) == {
        m["name"] for m in spec.metrics_of_cell(BM, CELL, "end_to_end")}
    assert all(c["value"] == 0 for c in doc["checks"].values())


@pytest.mark.parametrize("fault,failing", [
    ("drop_row", "rows_off_sequence"),      # a record never reaches the sink
    ("swap_rows", "rows_off_sequence"),     # per-source order broken
])
def test_a_broken_path_comes_out_not_correct(fault, failing):
    doc, stderr = _run(fault, seed="80")
    assert doc["correct"] is False
    assert doc["checks"][failing]["value"] > doc["checks"][failing]["limit"]
    assert f"check {failing}:" in stderr and "<-- FAILS" in stderr


def test_an_altered_field_comes_out_as_a_differing_record(tmp_path, source,
                                                          reference):
    """The field-by-field comparison on this configuration's records: a
    level altered between the sink and the comparison is a differing
    record, a record split at an embedded newline is too.  (The harness's
    own ``--fault alter_field`` rewrites a ``status`` member, which these
    records do not have: PERF.md, Open questions.)"""
    seqs = np.arange(40, 60)
    units = [u.tobytes()[:-1] for u in source.block_at(seqs)]
    good = b"".join(json.dumps(dict(reference.expected(u)[0],
                                    __time__=1700000000)).encode() + b"\n"
                    for u in units)

    def compare(sink: bytes) -> dict:
        (tmp_path / "tail.samples").write_bytes(sink)
        tail = {"sample_index": np.array([[0, 0, len(sink)]], np.int64)}
        return check.compare_samples(str(tmp_path), tail, source, reference,
                                     1700000000 - 1)
    assert compare(good) == dict(compare(good), compared=20, bad_record=0,
                                 bad_time=0)
    altered = good.replace(b'"level": "ERROR"', b'"level": "ERROS"', 1)
    assert altered != good and compare(altered)["bad_record"] == 1
    cut = good.replace(b"\\n\\tat ", b"\\n\\tat", 1)
    assert compare(cut)["bad_record"] == 1


# -- the four readers -----------------------------------------------------------------

def _obs(with_multiline: bool) -> dict:
    """A traced window whose slice of 2 s delivered 0.2 GB; ``with_multiline``
    False is a program with neither the spans, the section nor the module
    (the parent's)."""
    spans = [["processor.processor_split_multiline_log_string_native.dispatch",
              101.0, 0.30, 1, None, {}],
             ["processor.processor_parse_regex_tpu.complete", 101.5, 0.2, 9,
              None, {}]]
    events = [["/device:TPU:0", "XLA Ops", "%fusion.1 = x", 5e8, 1e6],
              ["/device:TPU:0", "XLA Modules", "jit_loong_extract_pallas(3)",
               2e8, 5e5]]
    status0, status1 = {}, {}
    if with_multiline:
        spans += [["multiline.classify.dispatch", 101.0, 0.25, 2, 1, {}],
                  ["device.pack", 101.05, 0.10, 3, 2, {}],
                  ["device.submit", 101.15, 0.05, 4, 2, {}],
                  ["multiline.classify.complete", 101.6, 0.02, 5, None, {}],
                  ["device.wait", 101.6, 0.005, 6, 5, {}],
                  ["multiline.merge", 101.7, 0.04, 7, None, {}],
                  ["multiline.merge", 102.7, 0.02, 8, None, {}]]
        events += [["/device:TPU:0", "XLA Modules",
                    "jit_loong_line_classify(5)", 1e8, 1e5],
                   ["/device:TPU:0", "XLA Modules",
                    "jit_loong_line_classify(5)", 9e8, 3e5],
                   ["/device:TPU:0", "XLA Modules",
                    "jit_loong_line_classify(5)", 3e9, 1e5]]    # after it
        row = {k: 0 for k in ("records_total", "unmatched_lines_total",
                              "carry_stitched_total", "carry_flushed_total",
                              "carry_oversize_total")}
        status0 = {"multiline": {"bench": dict(
            row, lines_total=1000, device_lines_total=400,
            host_lines_total=600, classify_calls={"256x256": 2})}}
        status1 = {"multiline": {"bench": dict(
            row, lines_total=11000, device_lines_total=9400,
            host_lines_total=1600,
            classify_calls={"256x256": 2, "8192x256": 30})}}
    return {
        "t0": 100.0, "t1": 110.0, "line_bytes": 1000,
        "tail": {"t": np.array([0.0, 100.0, 101.0, 103.0, 110.0]),
                 "last_seq": np.array([-1, -1, 99_999, 299_999, 999_999])},
        "slice": (101.0, 103.0), "spans": spans,
        "trace": {"events": events, "lo_ns": 0.0, "hi_ns": 2e9},
        "status0": status0, "status1": status1,
        "device": {"kind": "TPU v5 lite"}, "peaks": spec.load_peaks(),
    }


def _read(name, obs):
    return spec.load_module("metrics", name).read(obs)


READERS = ["ml_classify_s_per_GB", "ml_merge_s_per_GB",
           "ml_device_line_share", "ml_classify_roofline"]


def test_readers_give_numbers_where_the_program_has_their_source():
    obs = _obs(True)
    # self time: 0.25 less the two device legs, 0.02 less the wait
    assert _read("ml_classify_s_per_GB", obs) == pytest.approx(
        (0.25 - 0.15 + 0.02 - 0.005) / 0.2)
    assert _read("ml_merge_s_per_GB", obs) == pytest.approx(0.06 / 0.2)
    assert _read("ml_device_line_share", obs) == pytest.approx(9000 / 10000)
    mod = spec.load_module("metrics", "ml_classify_roofline")
    per_call = mod.call_bytes(8192, 256)
    assert per_call == 8192 * 256 + 4 * 8192 + 4 * 8192
    assert mod.read(obs) == pytest.approx(
        100 * (2 * per_call / 819e9) / 0.0004)
    assert 0 < mod.read(obs) < 100


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_on_a_program_without_their_source(name):
    assert _read(name, _obs(False)) is None
    bare = _obs(False)
    bare.update(spans=None, trace=None, slice=None, status0={}, status1={})
    assert _read(name, bare) is None


def test_roofline_reader_gives_nothing_without_a_call_in_the_slice():
    obs = _obs(True)
    obs["trace"]["events"] = obs["trace"]["events"][:2]
    assert _read("ml_classify_roofline", obs) is None
    obs = _obs(True)
    obs["status0"] = obs["status1"]             # no call between the scrapes
    assert _read("ml_classify_roofline", obs) is None
    assert _read("ml_device_line_share", obs) is None


def test_every_entry_of_this_pr_has_the_reader_and_the_cell():
    by_name = {m["name"]: m for m in BM["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "delivered_MBps"
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "metrics",
                                           name + ".py"))
    assert by_name["ml_classify_roofline"] == dict(
        by_name["ml_classify_roofline"], unit="%", better="higher",
        source="device_trace", layer="kernels")
    for name in ("extract_us_per_MiB", "extract_roofline"):
        assert {"regex512.backlog", CELL} <= set(by_name[name]["workloads"])
    # facts of the program, not pins: the ingest events are physical lines,
    # and the cell runs no timestamp stage, no fused chain and no JSON stage
    for name in ("gen_lead_min_MiB", "ts_column_row_share",
                 "fused_dispatch_share", "json_program_roofline"):
        assert CELL not in by_name[name]["workloads"]
