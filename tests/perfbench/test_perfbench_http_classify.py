"""The HTTP-event → URL-classification configuration's benchmark files (PR 38):
the line source is a pure function of (seed, j), 128 bytes a line with its
sequence number in the first columns, the pool holds the mix ``config.json``
states with at least 40 templates for every rule and for the default, the
plain reference gives hand-written lines of every rule, of the default and a
rejected line the record ``re`` gives by hand, a CPU rehearsal of the cell is
``correct`` and a broken path is not, the field-by-field comparison catches an
altered category, and the three readers give a number where the program has
their source and nothing (never 0) where it has not."""

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
CFG = spec.load_config(BM, "file_http_classify_url")
SEED = 2147483659
CELL = "http_classify.backlog"
WIDTH = CFG["source"]["line_bytes"]
KEYS = ["req_id", "pid", "comm", "local_addr", "remote_addr", "direction",
        "method", "path", "host", "http_version"]
# the list written out by hand, as re would be given it
BY_HAND = [
    ("health", rb"/healthz|/readyz|/livez|/metrics"),
    ("user_orders", rb"/api/v\d+/users/\d+/orders(/\d+)?(\?.*)?"),
    ("user", rb"/api/v\d+/users/\d+(\?.*)?"),
    ("order", rb"/api/v\d+/orders/\d+(\?.*)?"),
    ("search", rb"/api/v\d+/search\?.*"),
    ("auth", rb"/(login|logout|oauth/token)(\?.*)?"),
    ("api_other", rb"/api/v\d+/[a-z_]+(/[^?]*)?(\?.*)?"),
    ("static", rb"/static/[^?]*\.(js|css|png|svg|woff2)(\?.*)?"),
]
MIX = {"order": 901, "user": 614, "api_other": 614, "static": 491,
       "user_orders": 410, "search": 410, "health": 205, "auth": 123,
       "other": 287, "reject": 41}
READERS = ["classify_device_row_share", "classify_apply_s_per_GB",
           "classify_program_roofline"]
#: lists that took the cell after it was added: the threads' account, the
#: sender's share and the arrays a dispatch hands over
APPENDED = {"worker_cpu_share", "worker_offcpu_share", "reader_cpu_share",
            "device_copy_cpu_s_per_GB", "proc_stage_cpu_s_per_GB",
            "reader_round_s_per_GB", "enqueue_blocked_share",
            "flush_offload_share", "io_arrays_per_dispatch"}


@pytest.fixture(scope="module")
def source():
    return spec.load_module("sources", "http_event_templates").make(
        CFG["source"], SEED)


@pytest.fixture(scope="module")
def reference():
    return spec.load_module("references", "http_classify").make(
        CFG["reference"])


def _templates(source):
    return [source.templates[k].tobytes() for k in range(source.pool)]


def _by_hand(line: bytes) -> dict:
    tokens = line.split(b" ")
    if len(tokens) != 10 or b"" in tokens or not tokens[0].isdigit() \
            or not tokens[1].isdigit():
        return {"rawLog": line.decode("latin-1")}
    rec = {k: t.decode("latin-1") for k, t in zip(KEYS, tokens)}
    rec["category"] = next((name for name, rx in BY_HAND
                            if re.fullmatch(rx, tokens[7])), "other")
    return rec


# -- the line source --------------------------------------------------------------------

def test_source_is_a_pure_function_of_seed_and_line(source):
    mod = spec.load_module("sources", "http_event_templates")
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
    assert len(mod.make(CFG["source"], 2**31 + 12345).line(7)) == WIDTH


def test_every_line_is_128_bytes_with_its_sequence_in_the_first_columns(
        source):
    assert WIDTH == source.line_bytes == 128
    assert source.templates.shape == (4096, 128) and source.seq_offset == 0
    for k, line in enumerate(_templates(source)):
        assert len(line) == 128 and line.endswith(b"\n") and line.isascii()
        assert line.count(b"\n") == 1 and line[:13] == b"0" * 12 + b" "
        tokens = line[:-1].split(b" ")
        kind = source.kinds[k]
        assert len(tokens) == (9 if kind["kind"] == "reject" else 10), k
        assert b"" not in tokens
        if kind["kind"] not in ("reject", "health"):
            # the query string takes up the slack: never under 24 bytes
            assert len(tokens[7]) >= 24 and b"?" in tokens[7], k
            assert tokens[7] == kind["path"].encode()
    big = source.line(999_999_999_999)
    assert len(big) == 128 and big.startswith(b"999999999999 ")
    # the one change to the cell's data ISSUE 38 allows: at 256 bytes the
    # query string (a probe's host) takes up the slack, nothing else moves
    wide = spec.load_module("sources", "http_event_templates").make(
        dict(CFG["source"], line_bytes=256), SEED)
    assert wide.templates.shape == (4096, 256)
    assert [k["kind"] for k in wide.kinds] == [k["kind"] for k in source.kinds]


def test_pool_holds_the_mix_and_every_rule_meets_forty_templates(source,
                                                                 reference):
    kinds = [k["kind"] for k in source.kinds]
    assert len(kinds) == CFG["source"]["pool"] == 4096
    assert {k: kinds.count(k) for k in set(kinds)} == MIX
    assert sum(CFG["source"]["mix"].values()) == 100
    for kind, share in CFG["source"]["mix"].items():
        assert abs(MIX[kind] / 4096 - share / 100) < 0.001, kind
        assert MIX[kind] >= 40
    missing = [k["missing"] for k in source.kinds if k["kind"] == "reject"]
    assert sorted(set(missing)) == ["comm", "http_version"] \
        and abs(missing.count("comm") - missing.count("http_version")) <= 1
    # a class is the category the reference gives its lines, and re by hand
    for k, line in enumerate(_templates(source)):
        rec = reference.expected(line[:-1])[0]
        assert rec == _by_hand(line[:-1]), k
        want = kinds[k]
        assert rec.get("category") == (None if want == "reject" else want), k
    # first match wins shows: over half of the records' paths also fully
    # match a later rule than the one that names them
    later = 0
    for k, line in enumerate(_templates(source)):
        if kinds[k] in ("reject", "other"):
            continue
        path = line[:-1].split(b" ")[7]
        hits = [n for n, rx in BY_HAND if re.fullmatch(rx, path)]
        assert hits[0] == kinds[k]
        later += len(hits) > 1
    assert later / 4096 > 0.5
    for key in ("event_source", "record", "width", "rules", "mix", "sequence",
                "ascii", "pool", "cpu_usage_limit", "process_thread_count",
                "sink", "one_chip", "tier", "alter_field", "not_in_the_cell",
                "parent"):
        assert key in CFG["assumed"], key


def test_seqs_in_finds_every_records_line(source, reference):
    seqs = [5, 6, 7, 123456789012, 9] + \
        [j for j in range(4000) if source.kinds[
            int(source.template_of(j, 1)[0])]["kind"] == "reject"][:3]
    recs = [json.dumps(dict(reference.expected(
        source.block_at(np.array([j]))[0].tobytes()[:-1])[0],
        __time__=1700000000)) for j in seqs]
    assert any('"rawLog"' in r for r in recs)
    sink = ("\n".join(recs) + "\n").encode()
    assert source.seqs_in(sink).tolist() == seqs
    assert source.seqs_in(sink.replace(b'": "', b'":"')).tolist() == seqs
    assert source.seqs_in(b"").size == 0
    # as the agent's serializer lays a record out (__time__ first) the digits
    # are read by position — the tailer's cost a record is what bounds this
    # cell's harness — and any other layout falls back to the pattern
    mod = spec.load_module("sources", "http_event_templates")
    laid = ("\n".join(json.dumps(dict(
        {"__time__": 1700000000}, **reference.expected(source.block_at(
            np.array([j]))[0].tobytes()[:-1])[0])) for j in seqs)
        + "\n").encode()
    assert laid.startswith(b'{"__time__": 1700000000, "req_id": "000000000005"')
    assert mod._seqs_by_position(laid).tolist() == seqs \
        == source.seqs_in(laid).tolist()
    assert mod._seqs_by_position(sink) is None
    for broken in (laid[:-1], laid.replace(b"1700000000", b"170000000", 1),
                   b'{"a": 1}\n', b""):
        assert mod._seqs_by_position(broken) is None
    assert source.seqs_in(laid.replace(b"1700000000", b"170000000", 1)) \
        .tolist() == seqs


# -- the plain reference ------------------------------------------------------------------

HAND_LINES = [
    (b"000000000001 42 nginx 10.0.0.1:80 10.1.2.3:40000 ingress GET /healthz h 1.1",
     "health"),
    (b"000000000002 42 nginx 10.0.0.1:80 10.1.2.3:40000 ingress GET "
     b"/api/v1/users/7/orders/3?x=1 h 1.1", "user_orders"),
    (b"000000000003 42 nginx 10.0.0.1:80 10.1.2.3:40000 ingress PUT "
     b"/api/v2/users/7 h 2", "user"),
    (b"000000000004 42 nginx 10.0.0.1:80 10.1.2.3:40000 egress DELETE "
     b"/api/v1/orders/99?a=b h 1.1", "order"),
    (b"000000000005 42 java 10.0.0.1:80 10.1.2.3:40000 ingress GET "
     b"/api/v3/search?q=tpu h 1.1", "search"),
    (b"000000000006 42 java 10.0.0.1:80 10.1.2.3:40000 ingress POST "
     b"/oauth/token?grant=code h 1.1", "auth"),
    (b"000000000007 42 java 10.0.0.1:80 10.1.2.3:40000 ingress GET "
     b"/api/v1/search h 1.1", "api_other"),        # no ?: not the search rule
    (b"000000000008 42 node 10.0.0.1:80 10.1.2.3:40000 ingress GET "
     b"/static/js/app.min.js?v=3 h 1.1", "static"),
    (b"000000000009 42 node 10.0.0.1:80 10.1.2.3:40000 ingress GET "
     b"/wp-login.php h 1.0", "other"),
    (b"000000000010 42 node 10.0.0.1:80 10.1.2.3:40000 ingress GET "
     b"/healthz?verbose h 1.1", "other"),
]


def test_reference_on_hand_written_lines_of_every_rule_and_the_default(
        reference):
    seen = set()
    for line, want in HAND_LINES:
        rec, epoch = reference.expected(line)
        assert epoch is None
        assert rec == _by_hand(line) and list(rec) == KEYS + ["category"]
        assert rec["category"] == want and rec["req_id"] == line[:12].decode()
        seen.add(want)
    assert seen == {n for n, _ in BY_HAND} | {"other"}
    # a rejected line: a field missing, pid not a number
    for line in (b"000000000011 42 10.0.0.1:80 10.1.2.3:40000 ingress GET "
                 b"/healthz h 1.1",
                 b"000000000012 4x nginx 10.0.0.1:80 10.1.2.3:40000 ingress "
                 b"GET /healthz h 1.1"):
        assert reference.expected(line) == (
            {"rawLog": line.decode()}, None) == (_by_hand(line), None)
    # its rules are the configuration's own copy, in the pipeline's order
    assert [(r["name"], r["regex"].encode())
            for r in CFG["reference"]["rules"]] == BY_HAND
    text = open(os.path.join(spec.BENCH_DIR, "references",
                             "http_classify.py")).read()
    assert "loongcollector" not in text.split('"""', 2)[2]
    assert "import re\n" in text and "benchlib" not in text


def test_every_line_is_one_sink_record(source, reference):
    assert check.keep_mask(source, reference).all()


# -- the configuration, the traffic file, the cell, the entries -------------------------

def test_the_configuration_the_traffic_and_the_cell_are_there():
    cell = spec.find_cell(BM, CELL)
    assert cell == dict(cell, config="file_http_classify_url",
                        traffic="backlog128", chips=1)
    (entry,) = [c for c in BM["configs"]
                if c["name"] == "file_http_classify_url"]
    assert entry["reduced"] == [] == CFG["reduced"]
    assert "BASELINE.json config 5" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200 \
        and len(cell["why"]) <= 200
    assert len(CFG["guarantees"]) == 6 and any(
        "first rule of the list that fully matches path" in g
        for g in CFG["guarantees"])
    filt = spec.load_config(BM, "file_regex_filter_512")
    assert set(filt["guarantees"]) <= set(CFG["guarantees"])
    assert "needs_of_program" not in CFG["source"]
    # the traffic file is backlog.json with 4,096 lines a write
    mine, theirs = spec.load_traffic("backlog128"), spec.load_traffic("backlog")
    assert mine["write_lines"] == 4096 \
        and mine["write_lines"] * WIDTH == theirs["write_lines"] * 512
    assert {k: v for k, v in mine.items() if k not in ("why", "write_lines")} \
        == {k: v for k, v in theirs.items() if k not in ("why", "write_lines")}
    # the two files the ISSUE says are file_regex_apache_512's
    apache = os.path.join(spec.BENCH_DIR, "configs", "file_regex_apache_512")
    for name in ("loongcollector_config.json", "env.json"):
        assert open(os.path.join(CFG["dir"], name)).read() \
            == open(os.path.join(apache, name)).read()
    text = open(os.path.join(CFG["dir"], CFG["pipeline"])).read()
    assert text.index("processor_parse_regex_tpu") \
        < text.index("processor_classify_url_tpu") < text.index("flusher_file")
    assert f"Regex: '{CFG['reference']['regex']}'" in text
    at = 0
    for rule in CFG["reference"]["rules"]:       # the same list, in order
        at = text.index(f"- Name: {rule['name']}\n        "
                        f"Regex: '{rule['regex']}'", at)
    quick = open(os.path.join(spec.ROOT, "example_config", "quick_start",
                              "http_classify_url.yaml")).read()
    assert text.split("processors:")[1].split("flushers:")[0] \
        == quick.split("processors:")[1].split("flushers:")[0]


def test_the_cell_reports_what_the_backlog_cells_report_and_its_three():
    e2e = {m["name"] for m in spec.metrics_of_cell(BM, CELL, "end_to_end")}
    assert {"delivered_MBps", "setup_s"} <= e2e
    mine = {m["name"] for m in spec.metrics_of_cell(BM, CELL, "per_layer")}
    # its own three, what every other saturated cell reports, the fused
    # dispatch, and the lists that took the cell after it was added
    assert set(READERS) | {"fused_dispatch_share"} | APPENDED \
        | bm_entries.common_of_the_other_backlog_cells(BM, CELL) <= mine
    by_name = {m["name"]: m for m in BM["per_layer"]}
    layers = {m["layer"] for m in BM["per_layer"] if m["name"] not in READERS}
    for name in READERS:
        # by name, once, wherever later PRs put it: not by its place
        assert [m["name"] for m in BM["per_layer"]].count(name) == 1, name
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "delivered_MBps"
        assert m["layer"] in layers             # a layer the benchmark names
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "metrics",
                                           name + ".py"))
    assert by_name["classify_device_row_share"] == dict(
        by_name["classify_device_row_share"], source="program_counter",
        layer="routing", better="higher", unit="share")
    assert by_name["classify_apply_s_per_GB"] == dict(
        by_name["classify_apply_s_per_GB"], source="program_span",
        layer="processors", better="lower", unit="s/GB")
    assert by_name["classify_program_roofline"] == dict(
        by_name["classify_program_roofline"], source="device_trace",
        layer="kernels", better="higher", unit="%")
    assert "grok_nginx.backlog" \
        in by_name["grok_list_program_row_share"]["workloads"]


# -- whole runs on the CPU ------------------------------------------------------------

def _run(fault, seed="83"):
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
    ("dup_row", "rows_off_sequence"),       # a record twice
])
def test_a_broken_path_comes_out_not_correct(fault, failing):
    doc, stderr = _run(fault, seed="84")
    assert doc["correct"] is False
    assert doc["checks"][failing]["value"] > doc["checks"][failing]["limit"]
    assert f"check {failing}:" in stderr and "<-- FAILS" in stderr


def test_an_altered_category_comes_out_as_a_differing_record(
        tmp_path, source, reference):
    """The field-by-field comparison on this configuration's records: a
    category altered between the sink and the comparison is a differing
    record — the label of a later rule that also matches, the default, a
    category on a record that has no path, a category gone.  (The harness's
    own ``--fault alter_field`` rewrites a ``status`` member, which these
    records do not have.)"""
    seqs = np.arange(40, 240)
    lines = [u.tobytes()[:-1] for u in source.block_at(seqs)]
    good = b"".join(json.dumps(dict(reference.expected(u)[0],
                                    __time__=1700000000)).encode() + b"\n"
                    for u in lines)

    def compare(sink: bytes) -> dict:
        (tmp_path / "tail.samples").write_bytes(sink)
        tail = {"sample_index": np.array([[0, 0, len(sink)]], np.int64)}
        return check.compare_samples(str(tmp_path), tail, source, reference,
                                     1700000000 - 1)
    assert compare(good) == dict(compare(good), compared=200, bad_record=0,
                                 bad_time=0)
    for was, now in ((b'"category": "order"', b'"category": "api_other"'),
                     (b'"category": "user"', b'"category": "other"'),
                     (b', "category": "static"', b''),
                     (b'"category": "search"', b'"category": "Search"')):
        altered = good.replace(was, now, 1)
        assert altered != good and compare(altered)["bad_record"] == 1, was
    assert b'"rawLog"' in good
    labelled = re.sub(rb'("rawLog": "[^"]*")', rb'\1, "category": "other"',
                      good, count=1)
    assert labelled != good and compare(labelled)["bad_record"] == 1
    # the harness's own alter_field finds no such member to rewrite here
    assert b'"status": "' not in good


# -- the three readers -----------------------------------------------------------------

def _obs(with_source: bool) -> dict:
    """A traced window whose slice of 2 s delivered 0.2 GB; ``with_source``
    False is a program with neither the spans, the section nor the stage
    (the parent's)."""
    spans = [["processor.fused_chain.complete", 101.0, 0.30, 1, None, {}]]
    status0, status1 = {}, {"stage_fusion": {"programs": []}}
    events = [["/device:TPU:0", "XLA Ops", "%fusion.1", 101.2e9, 2e6]]
    if with_source:
        spans += [["classify.apply", 101.1, 0.03, 2, 1, {}],
                  ["classify.apply", 102.0, 0.05, 3, None, {}],
                  ["classify.host", 102.5, 0.5, 4, None, {}]]
        row = {"host_rows_total": 10, "default_rows_total": 7,
               "rule_rows_total": [1] * 8}
        status0 = {"classify_url": {"bench": dict(
            row, rows_total=1000, label_program_rows_total=900,
            absent_rows_total=90)},
            "stage_fusion": {"programs": [
                {"stages": ["extract:x", "label:y"], "signature": "abc",
                 "captures": [10, 0],
                 "geometry_dispatches": {"1024x128": 2, "2048x128": 1,
                                         "4096x128": 300}}]}}
        status1 = {"classify_url": {"bench": dict(
            row, rows_total=101000, label_program_rows_total=99900,
            absent_rows_total=1090)},
            "stage_fusion": {"programs": [
                {"stages": ["extract:processor_parse_regex_tpu",
                            "label:processor_classify_url_tpu"],
                 "signature": "abc", "captures": [10, 0],
                 # the warm-up's partial groups left two geometries behind
                 "geometries": ["1024x128", "2048x128", "4096x128"],
                 "geometry_dispatches": {"1024x128": 2, "2048x128": 1,
                                         "4096x128": 9000}},
                {"stages": ["extract:x", "filter"], "captures": [9, 0],
                 "geometries": ["1024x512"]}]}}
        events += [
            ["/device:TPU:0", "XLA Modules", "jit_loong_fused_program(1)",
             101.0e9 + i * 1e7, 2.0e6] for i in range(10)] + [
            # outside the slice: not counted
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
    assert _read("classify_device_row_share", obs) == pytest.approx(0.99)
    assert _read("classify_apply_s_per_GB", obs) == pytest.approx(0.08 / 0.2)
    mod = spec.load_module("metrics", "classify_program_roofline")
    # rows x width + 4 x rows in; the ok byte, 8 x captures and the label out
    assert mod.call_bytes(4096, 128, 10) \
        == 4096 * 128 + 4 * 4096 + 4096 * (1 + 80 + 4) == 888_832
    peak = spec.load_peaks()["TPU v5 lite"]["hbm_GBps"] * 1e9
    assert mod.read(obs) == pytest.approx(
        100.0 * (10 * 888_832 / peak) / (10 * 2.0e-3))
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
        program["geometry_dispatches"]["2048x128"] = 200    # 2 % of the calls
        assert _read(name, still) is None       # a call's shape is not known
        del program["geometry_dispatches"]      # a program that counts none
        assert _read(name, still) is None       # and ran three geometries
        program["geometries"] = ["4096x128"]
        assert _read(name, still) == pytest.approx(_read(name, _obs(True)))
        quiet = _obs(True)
        quiet["trace"]["events"] = quiet["trace"]["events"][:1]
        assert _read(name, quiet) is None       # no call fell in the slice
