"""The JSON configuration's benchmark files (PR 27): the line source is a
pure function of (seed, j) at a fixed width, its template classes are what
``config.json`` states, ``seqs_in`` finds every record's line, the plain
reference agrees with ``json.loads`` + ``re`` worked out here once more, and
the three readers give a number where the program has their source and
nothing (never 0) where it has not."""

import os
import sys

# the benchmark's library lives beside the benchmark, not in the program
sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench"))

import json
import re

import numpy as np
import pytest

import bm_entries
from benchlib import check, spec

BM = spec.load_benchmark()
CFG = spec.load_config(BM, "file_json_filter_1k")
SEED = 2147483659
CELL = "json1k_filter.backlog"


@pytest.fixture(scope="module")
def source():
    return spec.load_module("sources", "json_templates").make(
        CFG["source"], SEED)


@pytest.fixture(scope="module")
def reference():
    return spec.load_module("references", "json_filter").make(
        CFG["reference"])


def _templates(source):
    return [source.templates[k].tobytes() for k in range(source.pool)]


def test_source_is_a_pure_function_of_seed_and_line(source):
    mod = spec.load_module("sources", "json_templates")
    again = mod.make(CFG["source"], SEED)
    other = mod.make(CFG["source"], SEED + 1)
    assert np.array_equal(source.templates, again.templates)
    assert not np.array_equal(source.templates, other.templates)
    block = source.block(1000, 300)
    assert np.array_equal(block, again.block_at(np.arange(1000, 1300)))
    assert block.tobytes() == b"".join(source.line(j)
                                       for j in range(1000, 1300))
    j = np.array([7, 123456789012, 5, 7])
    assert np.array_equal(source.block_at(j)[0], source.block_at(j)[3])
    assert np.array_equal(source.template_of(1000, 300),
                          again.template_of(1000, 300))


def test_every_line_is_1024_bytes_with_its_newline(source):
    assert source.line_bytes == 1024 and source.templates.shape == (4096, 1024)
    for line in _templates(source):
        assert len(line) == 1024 and line.endswith(b"\n") \
            and b"\n" not in line[:-1]
    big = source.line(999_999_999_999)
    assert len(big) == 1024 and b'"seq":"999999999999"' in big


def test_class_counts_are_what_the_configuration_states(source):
    p = CFG["source"]
    mod = spec.load_module("sources", "json_templates")
    got = mod.class_counts(source)
    n_reject = round(p["pool"] * p["reject_share"])
    assert got["pool"] == p["pool"] == 4096 and got["reject"] == n_reject
    parsable = p["pool"] - n_reject
    total = sum(p["level_mix"].values())
    for level, weight in p["level_mix"].items():
        assert abs(got["levels"][level] - parsable * weight / total) < 1
    kept = sum(got["levels"][lv] for lv in p["keep_levels"])
    assert abs(kept / p["pool"] - 0.05) < 0.002            # the kept 5 %
    for cls, share in (("escape", "escape_share"), ("extra", "extra_key_share"),
                       ("missing", "missing_key_share")):
        want = round(kept * p[share]) + round((parsable - kept) * p[share])
        assert got[cls] == want, cls
    # three kinds of line that does not parse, a third each
    kinds = [k["reject"] for k in source.kinds if "reject" in k]
    assert {kinds.count(k) for k in set(kinds)} <= {13, 14} \
        and set(kinds) == {"truncated", "unbalanced", "array"}


def test_kept_templates_cover_what_each_side_of_the_program_produces(
        source, reference):
    kept = [k for k, kind in enumerate(source.kinds)
            if "reject" not in kind
            and kind["level"] in CFG["source"]["keep_levels"]]
    assert np.array_equal(np.flatnonzero(check.keep_mask(source, reference)),
                          np.array(kept))
    kinds = [source.kinds[k] for k in kept]
    assert sum("escape" in k for k in kinds) >= 10      # the host's emitter
    assert sum("extra" in k for k in kinds) >= 1        # drift, both ways
    assert sum("missing" in k for k in kinds) >= 1
    # every escape in turn, so every seed keeps as many lines of each
    turns = [k["escape"] for k in kinds if "escape" in k]
    assert {turns.count(v) for v in range(5)} == {4}
    plain = [k for k in kinds if len(k) == 1]           # the device stage
    assert len(plain) >= 150
    for k in kept[:20]:                                 # nested, two deep
        obj = json.loads(source.templates[k].tobytes())
        assert isinstance(obj["ctx"]["req"]["flags"], list) \
            and isinstance(obj["tags"], list)


def test_members_are_the_fourteen_the_configuration_lists(source):
    mod = spec.load_module("sources", "json_templates")
    for k, kind in enumerate(source.kinds):
        if "reject" in kind:
            continue
        names = json.loads(source.templates[k].tobytes(),
                           object_pairs_hook=lambda kv: [x for x, _ in kv])
        want = [n for n in mod.KEYS if not (n == "method" and "missing" in kind)]
        if "extra" in kind:
            want.insert(want.index("ctx"), "retry")
        assert names == want
    for name in mod.KEYS:
        assert name in CFG["assumed"]["members"]


def test_reference_agrees_with_json_loads_and_re(source, reference):
    rx = re.compile(CFG["reference"]["include"]["level"])
    n_kept = n_raw = 0
    for k, line in enumerate(_templates(source)):
        got = reference.expected(line[:-1])
        try:
            obj = json.loads(line)
        except ValueError:
            obj = None
        if not isinstance(obj, dict):
            assert "reject" in source.kinds[k] and got is None
            n_raw += 1
            continue
        assert "reject" not in source.kinds[k]
        if rx.fullmatch(obj["level"]) is None:
            assert got is None
            continue
        rec, epoch = got
        n_kept += 1
        assert epoch is None and list(rec) == list(obj)
        for name, v in obj.items():
            assert rec[name] == (v if isinstance(v, str) else json.dumps(
                v, ensure_ascii=False, separators=(",", ":")))
            if not isinstance(v, str):
                # the raw token is the compact text: no optional whitespace
                assert f'"{name}":{rec[name]}'.encode() in line
        if "escape" in source.kinds[k]:
            assert any(c in rec["msg"] + rec["path"]
                       for c in ('"', "\\", "\n", "é"))
    assert n_raw == 41 and n_kept == 203


def test_seqs_in_finds_every_records_line(source, reference):
    seqs, recs = [], []
    j = 0
    while len(recs) < 400:
        want = reference.expected(source.line(j)[:-1])
        if want is not None:
            seqs.append(j)
            recs.append(json.dumps(dict(want[0], __time__=1700000000)))
        j += 1
    sink = ("\n".join(recs) + "\n").encode()
    assert source.seqs_in(sink).tolist() == seqs
    assert source.seqs_in(b"").size == 0
    # and the lines made again from those numbers are the records' lines
    for line, rec in zip(source.block_at(np.array(seqs))[:50], recs[:50]):
        assert reference.expected(line.tobytes()[:-1])[0]["seq"] \
            == json.loads(rec)["seq"]


def test_the_cell_and_its_metrics_are_entries_alone():
    cell = spec.find_cell(BM, CELL)
    assert cell == dict(cell, config="file_json_filter_1k", traffic="backlog",
                        chips=1)
    e2e = {m["name"] for m in spec.metrics_of_cell(BM, CELL, "end_to_end")}
    assert {"delivered_MBps", "setup_s"} <= e2e
    mine = {m["name"] for m in spec.metrics_of_cell(BM, CELL, "per_layer")}
    # its own three, what every other saturated cell reports, and the fused
    # dispatch it shares with filter512.backlog
    assert {"json_host_row_share", "json_host_emit_s_per_GB",
            "json_program_roofline", "fused_dispatch_share"} \
        | bm_entries.common_of_the_other_backlog_cells(BM, CELL) <= mine
    assert CFG["reduced"] == [] and len(CFG["guarantees"]) == 5


# -- the three readers ---------------------------------------------------------------

def _obs(with_stage: bool) -> dict:
    """A traced window whose slice of 2 s delivered 0.2 GB through a
    512 x 1024 program; ``with_stage`` False is a program without the
    json_fields stage (the parent's)."""
    programs0 = programs1 = []
    fusion0, fusion1 = {"programs": []}, {"programs": []}
    spans = [["processor.fused_chain.complete", 101.0, 0.5, 1, None, {}]]
    events = [["/device:TPU:0", "XLA Ops", "%fusion.1 = x", 5e8, 1e6],
              ["/device:TPU:0", "XLA Modules", "jit_loong_fused_program(7)",
               1e8, 1e6],
              ["/device:TPU:0", "XLA Modules", "jit_loong_fused_program(7)",
               9e8, 3e6],
              ["/device:TPU:0", "XLA Modules", "jit_loong_fused_program(7)",
               3e9, 1e6]]                                  # after the slice
    if with_stage:
        row = {"stages": ["json_fields:processor_parse_json_tpu", "filter"],
               "captures": [17, 0], "geometries": ["512x1024"]}
        fusion0 = {"programs": [row], "json": {
            "rows_total": 1000, "signatures_decoded_total": 3,
            "host_rows_total": {"escape": 90, "shape": 10, "not_object": 4,
                                "overlong": 0}}}
        fusion1 = {"programs": [row], "json": {
            "rows_total": 11000, "signatures_decoded_total": 3,
            "host_rows_total": {"escape": 1090, "shape": 60, "not_object": 44,
                                "overlong": 0}}}
        spans += [["json.host_emit", 101.1, 0.03, 2, 1, {"rows": 56}],
                  ["json.host_emit", 101.3, 0.01, 3, 1, {"rows": 50}]]
    return {
        "t0": 100.0, "t1": 110.0, "line_bytes": 1000,
        "tail": {"t": np.array([0.0, 100.0, 101.0, 103.0, 110.0]),
                 "last_seq": np.array([-1, -1, 99_999, 299_999, 999_999])},
        "slice": (101.0, 103.0), "spans": spans,
        "trace": {"events": events, "lo_ns": 0.0, "hi_ns": 2e9},
        "status0": {"stage_fusion": fusion0},
        "status1": {"stage_fusion": fusion1},
        "device": {"kind": "TPU v5 lite"}, "peaks": spec.load_peaks(),
    }


def _read(name, obs):
    return spec.load_module("metrics", name).read(obs)


def test_readers_give_numbers_where_the_program_has_the_stage():
    obs = _obs(True)
    assert _read("json_host_row_share", obs) == pytest.approx(1090 / 10000)
    assert _read("json_host_emit_s_per_GB", obs) == pytest.approx(0.04 / 0.2)
    mod = spec.load_module("metrics", "json_program_roofline")
    per_call = mod.call_bytes(512, 1024, 17, 1)
    assert per_call == 512 * 1024 + 4 * 512 + 512 * (1 + 8 * 17 + 16) + 512
    assert mod.read(obs) == pytest.approx(
        100 * (2 * per_call / 819e9) / 0.004)


@pytest.mark.parametrize("name", ["json_host_row_share",
                                  "json_host_emit_s_per_GB",
                                  "json_program_roofline"])
def test_readers_give_nothing_on_a_program_without_the_stage(name):
    assert _read(name, _obs(False)) is None
    bare = _obs(False)
    bare.update(spans=None, trace=None, slice=None, status0={}, status1={})
    assert _read(name, bare) is None


def test_roofline_reader_gives_nothing_where_a_calls_shape_is_not_known():
    obs = _obs(True)
    obs["status1"]["stage_fusion"]["programs"][0]["geometries"] = [
        "256x1024", "512x1024"]
    assert _read("json_program_roofline", obs) is None
    obs = _obs(True)
    obs["trace"]["events"] = obs["trace"]["events"][:1]     # no call in it
    assert _read("json_program_roofline", obs) is None


def test_this_checkout_holds_what_the_configuration_needs():
    mod = spec.load_module("sources", "json_templates")
    mod.hold_program_to(CFG["source"]["needs_of_program"])


@pytest.mark.parametrize("parse_json", [
    None,                                       # no such file
    "class ProcessorParseJson:\n    def process(self, group):\n        ...\n",
])
def test_a_checkout_without_the_device_stage_is_refused(tmp_path, parse_json):
    """The benchmark's files laid over a commit whose JSON plugin parses on
    the host: the cell fails at once, as a SpecError (run.py's exit code 1),
    and does not measure the host plane under this cell's name."""
    mod = spec.load_module("sources", "json_templates")
    needs = CFG["source"]["needs_of_program"]
    if parse_json is not None:
        path = tmp_path / needs["file"]
        path.parent.mkdir(parents=True)
        path.write_text(parse_json)
    with pytest.raises(spec.SpecError, match="cannot run this configuration"):
        mod.hold_program_to(needs, root=str(tmp_path))
