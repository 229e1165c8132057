"""The credential-masking configuration's benchmark files: the line
source is a pure function of (seed, j), 512 bytes a line with its sequence in
the captured url before the ``?``, the pool holds the mix ``config.json``
states; the plain reference gives hand-checked records (masked url, rawLog
unmasked); a CPU rehearsal of the cell is ``correct`` and the stream controls
bite; a credential left unmasked between the sink and the comparison is a
differing record; the three readers give a number where the program has their
source and nothing (never 0) where it has not; a checkout without the fused
mask stage is refused at once."""

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
CONFIG = "file_desens_apache_512"
CFG = spec.load_config(BM, CONFIG)
SEED = 2147483659
CELL = "desens512.backlog"
MIX = {"both": 3277, "signature": 327, "sts": 205, "none": 164, "edge": 82,
       "reject": 41}
MATCHES = {"both": 2, "signature": 1, "sts": 3, "none": 0, "reject": 0,
           "empty_signature": 1, "last_parameter": 2, "look_alike": 2,
           "encoded": 1}
PAIR = rb"((?:AccessKeyId|Signature|SecurityToken)=)[^&]+"
READERS = ["desens_device_row_share", "desens_apply_s_per_GB",
           "desens_program_roofline"]


@pytest.fixture(scope="module")
def source():
    return spec.load_module("sources", "apache_secret_templates").make(
        CFG["source"], SEED)


@pytest.fixture(scope="module")
def reference():
    return spec.load_module("references", "desens_line").make(
        CFG["reference"])


# -- the line source ------------------------------------------------------------

def test_source_is_a_pure_function_of_seed_and_line(source):
    mod = spec.load_module("sources", "apache_secret_templates")
    again = mod.make(CFG["source"], SEED)
    assert np.array_equal(source.templates, again.templates)
    assert not np.array_equal(source.templates,
                              mod.make(CFG["source"], SEED + 1).templates)
    block = source.block(1000, 100)
    assert np.array_equal(block, again.block_at(np.arange(1000, 1100)))
    assert block.tobytes() == b"".join(source.line(j)
                                       for j in range(1000, 1100))
    # a seed beyond 32 signed bits, as a benchmark run's may be
    assert len(mod.make(CFG["source"], 2**31 + 12345).line(7)) == 512


def test_every_line_is_512_bytes_with_its_sequence_in_the_url(source):
    assert source.line_bytes == 512 and source.templates.shape == (4096, 512)
    for k in range(source.pool):
        line = source.templates[k].tobytes()
        assert len(line) == 512 and line.endswith(b"\n") and line.isascii()
        assert line.count(b"\n") == 1
        at = line.index(b"/api/v1/resource/")
        assert at + 17 == source.seq_offset
        assert line[at + 29:at + 30] == b"?"
    line = source.line(123456789012)
    assert b"/api/v1/resource/123456789012?" in line
    assert source.seqs_in(line).tolist() == [123456789012]


def test_the_pool_holds_the_mix_and_each_class_its_matches(source):
    kinds = [k["kind"] for k in source.kinds]
    assert {k: kinds.count(k) for k in set(kinds)} == MIX
    assert sum(CFG["source"]["mix"].values()) == 100
    for kind, share in CFG["source"]["mix"].items():
        assert abs(MIX[kind] / 4096 - share / 100) < 0.001, kind
    edges = [k["edge"] for k in source.kinds if k["edge"]]
    assert sorted({e: edges.count(e) for e in set(edges)}.values()) \
        == [20, 20, 21, 21]
    parse = re.compile(CFG["reference"]["regex"].encode())
    total = 0
    for k, kind in enumerate(source.kinds):
        line = source.templates[k].tobytes()[:-1]
        m = parse.fullmatch(line)
        if kind["kind"] == "reject":
            assert m is None and b"AccessKeyId=" in line
            continue
        url = m.group(6)
        n = len(re.findall(PAIR, url))
        assert n == MATCHES[kind["edge"] or kind["kind"]], (k, url)
        total += n
        if kind["edge"] == "last_parameter":
            assert re.search(rb"Signature=[^&]+$", url)
        if kind["edge"] == "empty_signature":
            assert b"Signature=&" in url or url.endswith(b"Signature=")
        if kind["edge"] == "encoded":
            assert b"%26Signature%3D" in url
        if kind["edge"] == "look_alike":
            assert b"XAccessKeyId=" in url
    # some 1.86 matches a row, as config.json says
    assert abs(total / 4096 - 1.86) < 0.01


# -- the plain reference ----------------------------------------------------------

HEAD = (b'10.0.0.1 - u1 [10/Oct/2000:13:55:36 -0700] "GET '
        b'/api/v1/resource/000000000042?')


def test_reference_on_hand_checked_lines(reference):
    cases = [
        (b"AccessKeyId=LTAIabc&Date=1&Signature=x%2Fy%3D",
         "AccessKeyId=********&Date=1&Signature=********"),
        (b"q=1&Signature=&AccessKeyId=k", "q=1&Signature=&AccessKeyId=********"),
        (b"XAccessKeyId=v&ref=a%26Signature%3Db",
         "XAccessKeyId=********&ref=a%26Signature%3Db"),
        (b"SecurityToken=t0&q=2", "SecurityToken=********&q=2"),
        (b"AccessKeyId=abcSignature=xyz", "AccessKeyId=********"),
        (b"q=1", "q=1"),
    ]
    for query, want in cases:
        line = HEAD + query + b' HTTP/1.1" 200 1234'
        rec, epoch = reference.expected(line)
        assert rec["url"] == "/api/v1/resource/000000000042?" + want
        assert rec["status"] == "200" and rec["protocol"] == "HTTP/1.1"
        assert epoch is not None
    # a line the pattern rejects ships whole, credentials and all
    bad = HEAD.replace(b"[", b"(") + b'Signature=s HTTP/1.1" 200 1'
    assert reference.expected(bad) == ({"rawLog": bad.decode()}, None)
    text = open(os.path.join(spec.BENCH_DIR, "references",
                             "desens_line.py")).read()
    assert "loongcollector" not in text.split('"""', 2)[2]


def test_every_line_is_one_sink_record(source, reference):
    assert check.keep_mask(source, reference).all()


# -- the configuration, the cell, the entries ------------------------------------------

def test_the_configuration_and_the_cell_are_entries_found_by_name():
    cell = spec.find_cell(BM, CELL)
    assert cell == dict(cell, config=CONFIG, traffic="backlog", chips=1)
    (entry,) = [c for c in BM["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [] == CFG["reduced"]
    assert "ProcessorDesensitizeNative.cpp" in entry["source"]
    for text in (entry["source"], entry["why"], cell["why"]):
        assert len(text) <= 200
    assert CFG["source"]["needs_of_program"]["holds"] == "def fused_stage_spec"
    apache = os.path.join(spec.BENCH_DIR, "configs", "file_regex_apache_512")
    for name in ("loongcollector_config.json", "env.json"):
        assert open(os.path.join(CFG["dir"], name)).read() \
            == open(os.path.join(apache, name)).read()
    text = open(os.path.join(CFG["dir"], CFG["pipeline"])).read()
    assert text.index("Type: processor_parse_regex_tpu") \
        < text.index("Type: processor_desensitize_native") \
        < text.index("Type: processor_parse_timestamp_native") \
        < text.index("Type: flusher_file")
    assert f"Regex: '{CFG['reference']['regex']}'" in text
    ref = CFG["reference"]
    for key, value in (("ContentPatternBeforeReplacedString", ref["before"]),
                       ("ReplacedContentPattern", ref["content"]),
                       ("ReplacingString", ref["replacing"])):
        assert f"{key}: '{value}'" in text
    quick = open(os.path.join(spec.ROOT, "example_config", "quick_start",
                              "desensitize_url.yaml")).read()
    assert text.split("processors:")[1].split("flushers:")[0] \
        == quick.split("processors:")[1].split("flushers:")[0]


def test_the_cell_reports_what_the_backlog_cells_report_and_its_three():
    e2e = {m["name"] for m in spec.metrics_of_cell(BM, CELL, "end_to_end")}
    assert {"delivered_MBps", "setup_s"} <= e2e
    mine = {m["name"] for m in spec.metrics_of_cell(BM, CELL, "per_layer")}
    filt = {m["name"] for m in spec.metrics_of_cell(BM, "filter512.backlog",
                                                    "per_layer")}
    assert set(READERS) | filt | {"ts_column_row_share",
                                  "ts_native_row_share"} \
        | bm_entries.common_of_the_other_backlog_cells(BM, CELL) <= mine
    by_name = {m["name"]: m for m in BM["per_layer"]}
    for name, layer, unit, source, better in (
            ("desens_device_row_share", "routing", "share",
             "program_counter", "higher"),
            ("desens_apply_s_per_GB", "processors", "s/GB", "program_span",
             "lower"),
            ("desens_program_roofline", "kernels", "%", "device_trace",
             "higher")):
        assert [m["name"] for m in BM["per_layer"]].count(name) == 1
        m = by_name[name]
        assert m == dict(m, layer=layer, unit=unit, source=source,
                         better=better, moves="delivered_MBps")
        assert CELL in m["workloads"]
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "metrics",
                                           name + ".py"))


def test_a_checkout_without_the_mask_stage_is_refused_at_once(tmp_path):
    mod = spec.load_module("sources", "json_templates")
    needs = CFG["source"]["needs_of_program"]
    target = tmp_path / needs["file"]
    target.parent.mkdir(parents=True)
    target.write_text("class ProcessorDesensitize:\n    pass\n")
    with pytest.raises(spec.SpecError, match="fused_stage_spec"):
        mod.hold_program_to(needs, root=str(tmp_path))
    target.write_text("    def fused_stage_spec(self, ctx):\n")
    mod.hold_program_to(needs, root=str(tmp_path))
    mod.hold_program_to(needs)                  # this checkout has it


# -- whole runs on the CPU ----------------------------------------------------------

def _run(fault, seed="2147483911"):
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
    assert all(c["value"] == 0 for c in doc["checks"].values())


@pytest.mark.parametrize("fault,failing", [
    ("drop_row", "rows_off_sequence"),      # a record never reaches the sink
    ("swap_rows", "rows_off_sequence"),     # per-source order broken
    ("alter_field", "records_differ"),      # a status rewritten
])
def test_a_broken_path_comes_out_not_correct(fault, failing):
    doc, stderr = _run(fault, seed="2147483912")
    assert doc["correct"] is False
    assert doc["checks"][failing]["value"] > doc["checks"][failing]["limit"]
    assert f"check {failing}:" in stderr and "<-- FAILS" in stderr


def test_a_credential_left_unmasked_is_a_differing_record(tmp_path, source,
                                                          reference):
    """The field-by-field comparison on this configuration's records: a
    value the mask missed — a credential shipped whole, one of two left, the
    mask's string changed, a masked rawLog — is a differing record."""
    seqs = np.arange(40, 240)
    lines = [u.tobytes()[:-1] for u in source.block_at(seqs)]
    good = b"".join(json.dumps(dict(reference.expected(u)[0],
                                    __time__=reference.expected(u)[1]
                                    or 1700000000)).encode() + b"\n"
                    for u in lines)

    def compare(sink: bytes) -> dict:
        (tmp_path / "tail.samples").write_bytes(sink)
        tail = {"sample_index": np.array([[0, 0, len(sink)]], np.int64)}
        return check.compare_samples(str(tmp_path), tail, source, reference,
                                     1700000000 - 1)
    assert compare(good) == dict(compare(good), compared=200, bad_record=0,
                                 bad_time=0)
    k = next(i for i, u in enumerate(lines) if b"&Signature=" in u
             and b"AccessKeyId=" in u and b"rawLog" not in u)
    unmasked = re.search(rb"Signature=([^&\s]+)", lines[k]).group(1)
    one = good.splitlines(keepends=True)
    for was, now in ((b"Signature=********", b"Signature=" + unmasked),
                     (b"AccessKeyId=********", b"AccessKeyId=*******"),
                     (b"=********", b"=XXXXXXXX")):
        altered = list(one)
        altered[k] = one[k].replace(was, now, 1)
        assert altered[k] != one[k]
        assert compare(b"".join(altered))["bad_record"] == 1, was
    raw = next(i for i, ln in enumerate(one) if b'"rawLog"' in ln)
    masked = list(one)
    masked[raw] = re.sub(rb"(Signature=)[^&\s]+", rb"\1********", one[raw],
                         count=1)
    assert masked[raw] != one[raw] and compare(b"".join(masked))[
        "bad_record"] == 1


# -- the three readers ----------------------------------------------------------------

def _obs(with_source: bool) -> dict:
    """A traced window whose slice of 2 s delivered 0.2 GB; ``with_source``
    False is a program with neither the spans, the section nor the stage
    (the parent's)."""
    spans = [["processor.fused_chain.complete", 101.0, 0.30, 1, None, {}]]
    status0, status1 = {}, {"stage_fusion": {"programs": []}}
    events = [["/device:TPU:0", "XLA Ops", "%fusion.1", 101.2e9, 2e6]]
    if with_source:
        spans += [["desensitize.apply", 101.1, 0.03, 2, 1, {}],
                  ["desensitize.apply", 102.0, 0.05, 3, None, {}],
                  ["desensitize.host", 102.5, 0.5, 4, None, {}]]
        host = {"tier": 0, "over_slots": 0, "non_ascii": 0, "no_fusion": 10,
                "demoted": 0}
        status0 = {"desensitize": {"bench": {
            "rows_total": 1000, "mask_program_rows_total": 980,
            "absent_rows_total": 10, "host_rows_total": host,
            "masked_rows_total": 900, "matches_total": 1800}},
            "stage_fusion": {"programs": [
                {"stages": ["extract:x", "mask:y"], "signature": "abc",
                 "captures": [9, 0], "slots": [0, 4],
                 "geometry_dispatches": {"512x512": 2, "1024x512": 300}}]}}
        status1 = {"desensitize": {"bench": {
            "rows_total": 101000, "mask_program_rows_total": 99980,
            "absent_rows_total": 1010, "host_rows_total": host,
            "masked_rows_total": 95000, "matches_total": 188000}},
            "stage_fusion": {"programs": [
                {"stages": ["extract:processor_parse_regex_tpu",
                            "mask:processor_desensitize_native"],
                 "signature": "abc", "captures": [9, 0], "slots": [0, 4],
                 "geometries": ["512x512", "1024x512"],
                 "geometry_dispatches": {"512x512": 2, "1024x512": 9000}},
                {"stages": ["extract:x", "filter"], "captures": [9, 0],
                 "geometries": ["1024x512"]}]}}
        events += [
            ["/device:TPU:0", "XLA Modules", "jit_loong_fused_program(1)",
             101.0e9 + i * 1e7, 0.5e6] for i in range(10)] + [
            ["/device:TPU:0", "XLA Modules", "jit_loong_fused_program(1)",
             104.0e9, 9.0e6]]
    return {
        "t0": 100.0, "t1": 110.0, "line_bytes": 1000,
        "tail": {"t": np.array([0.0, 100.0, 101.0, 103.0, 110.0]),
                 "last_seq": np.array([-1, -1, 99_999, 299_999, 999_999])},
        "slice": (101.0, 103.0), "spans": spans,
        "status0": status0, "status1": status1,
        "trace": {"events": events, "lo_ns": 101.0e9, "hi_ns": 103.0e9},
        "peaks": spec.load_peaks(), "device": {"kind": "TPU v5 lite"},
    }


def _read(name, obs):
    return spec.load_module("metrics", name).read(obs)


def test_readers_give_numbers_where_the_program_has_their_source():
    obs = _obs(True)
    assert _read("desens_device_row_share", obs) == pytest.approx(0.99)
    assert _read("desens_apply_s_per_GB", obs) == pytest.approx(0.08 / 0.2)
    mod = spec.load_module("metrics", "desens_program_roofline")
    # rows x width + 4 x rows in; the ok byte, 8 x captures, 8 x slots and
    # the count out
    assert mod.call_bytes(1024, 512, 9, 4) \
        == 1024 * 512 + 4 * 1024 + 1024 * (1 + 72 + 32 + 4) == 640_000
    peak = spec.load_peaks()["TPU v5 lite"]["hbm_GBps"] * 1e9
    assert mod.read(obs) == pytest.approx(
        100.0 * (10 * 640_000 / peak) / (10 * 0.5e-3))
    assert 0 < mod.read(obs) < 100


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_on_a_program_without_their_source(name):
    assert _read(name, _obs(False)) is None
    bare = _obs(False)
    bare.update(spans=None, slice=None, status0=None, status1=None, trace=None)
    assert _read(name, bare) is None
    still = _obs(True)
    if name.endswith("_share"):
        still["status0"] = still["status1"]     # no row between the scrapes
        assert _read(name, still) is None
    if name.endswith("_roofline"):
        program = still["status1"]["stage_fusion"]["programs"][0]
        program["geometry_dispatches"]["512x512"] = 200    # 2 % of the calls
        assert _read(name, still) is None       # a call's shape is not known
        del program["slots"]                    # a program that names none
        assert _read(name, still) is None
        quiet = _obs(True)
        quiet["trace"]["events"] = quiet["trace"]["events"][:1]
        assert _read(name, quiet) is None       # no call fell in the slice
