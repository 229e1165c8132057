"""The resident JSON stage (ops/kernels/json_fields.py) against the plain
reference, and the ``json → filter`` fused run it belongs to.

The reference is ``json.loads`` + ``re.fullmatch``, independent of the
program: a top-level string is its decoded text, any other value the JSON
value its raw token stands for, a row without the filter's key is dropped.
Every row either agrees with it field for field or carries a non-ok status;
for each adversarial class the expected outcome is stated here.
"""

import json
import random
import re

import numpy as np
import pytest

from loongcollector_tpu import models
from loongcollector_tpu.ops import device_stream
from loongcollector_tpu.ops import fused_pipeline as fp
from loongcollector_tpu.ops.device_plane import DevicePlane
from loongcollector_tpu.ops.kernels import json_fields as jf

from test_fused_pipeline import build_pipeline, make_group, process_one

OK, ESCAPE, SHAPE, NOT_OBJECT = (jf.STATUS_OK, jf.STATUS_ESCAPE,
                                 jf.STATUS_SHAPE, jf.STATUS_NOT_OBJECT)
INCLUDE = "ERROR|WARN"


@pytest.fixture(autouse=True)
def _fused_env(monkeypatch):
    """Fusion forced on (the CPU backend would leave it off), fresh device
    plane, ring and program cache per test."""
    monkeypatch.setenv("LOONG_FUSED", "1")
    prev = models.set_columnar_enabled(True)
    DevicePlane.reset_for_testing()
    device_stream.reset_for_testing()
    fp.reset_for_testing()
    yield
    models.set_columnar_enabled(prev)
    DevicePlane.reset_for_testing()
    device_stream.reset_for_testing()
    fp.reset_for_testing()


# -- the plain reference --------------------------------------------------------

def reference(line: bytes):
    """{key: value} of a row that parses as an object — a string as its
    decoded text, any other value as the Python value it stands for — or
    None for a row that is no JSON object."""
    try:
        obj = json.loads(line)
    except ValueError:
        return None
    return obj if isinstance(obj, dict) else None


def reference_keeps(line: bytes, key: str = "level",
                    pattern: str = INCLUDE) -> bool:
    obj = reference(line)
    if obj is None or key not in obj:
        return False
    v = obj[key]
    text = v if isinstance(v, str) else json.dumps(v, separators=(",", ":"))
    return re.fullmatch(pattern, text) is not None


def agrees(got: dict, want: dict) -> bool:
    """The program's {key: bytes} against the reference's object: a string
    byte for byte, any other value as a raw token (trimmed) that stands
    for the same JSON value."""
    if set(got) != set(want):
        return False
    for k, v in want.items():
        raw = got[k]
        if isinstance(v, str):
            if raw != v.encode("utf-8"):
                return False
        elif raw != raw.strip() or json.loads(raw) != v:
            return False
    return True


# -- driving the stage ------------------------------------------------------------

def run_stage(lines, L, bound=("level",)):
    plan = jf.JsonFieldsPlan()
    for name in bound:
        plan.bind(name)
    rows = np.zeros((len(lines), L), np.uint8)
    lens = np.zeros(len(lines), np.int32)
    for i, ln in enumerate(lines):
        rows[i, :min(len(ln), L)] = np.frombuffer(ln[:L], np.uint8)
        lens[i] = len(ln)
    out = [np.asarray(a) for a in jf.JsonFieldsKernel(plan)(rows, lens)]
    return plan, out


def fields_of(line, i, out, names):
    _ok, off, ln, _status, members, _sig = out
    got = {}
    for k, name in enumerate(names[:int(members[i])]):
        got[name] = line[int(off[i, k]):int(off[i, k]) + int(ln[i, k])]
    return got


def key_names(line):
    return json.loads(line, object_pairs_hook=lambda kv: [k for k, _ in kv])


def check_rows(lines, L, expect_status=None):
    """Every row agrees with the reference or is not ok; returns statuses."""
    plan, out = run_stage(lines, L)
    ok, off, ln, status, members, sig = out
    for i, line in enumerate(lines):
        want = reference(line)
        if ok[i]:
            assert want is not None, line
            names = key_names(line)
            assert int(members[i]) == len(names), line
            assert agrees(fields_of(line, i, out, names), want), line
            cap = plan.kmax                      # the named capture: level
            if "level" in want:
                got = line[int(off[i, cap]):int(off[i, cap]) + int(ln[i, cap])]
                assert agrees({"level": got}, {"level": want["level"]}), line
            else:
                assert ln[i, cap] == -1, line
        else:
            assert (ln[i] == -1).all(), line     # publishes no span
        if expect_status is not None:
            assert status[i] == expect_status[i], \
                (line, jf.STATUS_NAMES[status[i]])
    return status


# -- the adversarial classes, each with the outcome it must have -------------------

CLASSES = [
    # (row, status it must carry)
    (b'{"a":"x\\"y","level":"WARN"}', ESCAPE),               # \"
    (b'{"a":"x\\\\","level":"WARN"}', ESCAPE),               # \\ then the closing quote
    (b'{"a":"x\\\\\\"y","level":"ERROR"}', ESCAPE),          # \\\"
    ('{"a":"é","level":"ERROR"}'.encode(), OK),              # é, raw UTF-8
    (b'{"a":"\\u00e9","level":"ERROR"}', ESCAPE),            # é, escaped
    (b'{"m":"{a:[1,2],\\"b\\"}","level":"INFO"}', ESCAPE),   # structure in a string, escaped quotes
    (b'{"m":"{a:[1,2], b}:,","level":"INFO"}', OK),          # braces, colons, commas in a string
    (b'{"ctx":{"a":{"b":[1,2]},"c":"x"},"level":"WARN"}', OK),   # nested two deep
    (b'{"arr":[[1,2],[3,{"z":null}]],"level":"WARN"}', OK),
    (b'{"a":"","level":""}', OK),                            # empty strings
    (b'{}', OK),                                             # empty object
    (b'{"ctx":{},"arr":[],"level":"ERROR"}', OK),
    (b' { "a" : 1 , "level" : "WARN" , "c" : [ 1 , 2 ] } ', OK),   # whitespace everywhere
    (b'\t{"a":\ttrue,\r"b":null}\n', OK),
    (b'{"ctx":{"level":"ERROR"},"n":1}', OK),                # bound key nested: must not bind
    (b'{"n":1,"level":"ERROR"}', OK),                        # bound key second
    (b'{"level":"INFO","level":"ERROR"}', SHAPE),            # bound key twice
    (b'{"level":"ERROR","extra":1,"n":2}', OK),              # a key more
    (b'{"n":2}', OK),                                        # a key fewer
    (b'{"a":1,"level":"WA', SHAPE),                          # truncated in a string
    (b'{"a":1,"level":"WARN"', SHAPE),                       # truncated before the brace
    (b'{"a":[1,2,"level":"WARN"}', SHAPE),                   # unbalanced
    (b'{"a":[1,2}],"level":"WARN"}', SHAPE),                 # crossed brackets
    (b'{"a":1}{"b":2}', SHAPE),                              # trailing bytes
    (b'{"a":1} x', SHAPE),
    (b'[{"level":"ERROR"}]', NOT_OBJECT),                    # bare array
    (b'"level"', NOT_OBJECT),
    (b'level=ERROR', NOT_OBJECT),
    (b'', NOT_OBJECT),
    (b'{"a":tru,"level":"ERROR"}', SHAPE),                   # invalid scalars
    (b'{"a":01}', SHAPE),
    (b'{"a":1.2.3}', SHAPE),
    (b'{"a":1e5e3}', SHAPE),
    (b'{"a":-}', SHAPE),
    (b'{"a":.5}', SHAPE),
    (b'{"a":1 2}', SHAPE),
    (b'{"a":-0.5e+3,"b":1E9,"c":0,"d":-0,"e":10.25}', OK),
    (b'{"a", "b"}', SHAPE),                                  # grammar
    (b'{"a":["x":1]}', SHAPE),
    (b'{"a":{"x"}}', SHAPE),
    (b'{"a":1,}', SHAPE),
    (b'{"a":[1,]}', SHAPE),
    (b'{,"a":1}', SHAPE),
    (b'{"a" 1}', SHAPE),
    (b'{"a"::1}', SHAPE),
    (b'{a:1}', SHAPE),
    (b'{"a":"x" "y"}', SHAPE),
    (b'{"a":"x\ty"}', SHAPE),                                # a control byte in a string
    (b'{"a":\\n1}', SHAPE),                                  # a backslash outside a string
    (b'{"a":[[[[1]]]]}', SHAPE),                             # deeper than DMAX
    (b'{"a":[[[1]]]}', OK),                                  # at DMAX
    (b'{' + b",".join(b'"k%d":%d' % (i, i) for i in range(jf.KMAX)) + b'}', OK),
    (b'{' + b",".join(b'"k%d":%d' % (i, i) for i in range(jf.KMAX + 1)) + b'}',
     SHAPE),                                                 # more than KMAX members
]


@pytest.mark.parametrize("L", [128, 256])
def test_adversarial_classes_have_the_stated_outcome(L):
    lines = [c[0] for c in CLASSES]
    check_rows(lines, L, [c[1] for c in CLASSES])


def test_what_the_device_proves_is_what_json_loads_accepts():
    """An ok row parses; a row json.loads rejects is never ok."""
    lines = [c[0] for c in CLASSES]
    _plan, out = run_stage(lines, 256)
    for line, ok in zip(lines, out[0]):
        if reference(line) is None:
            assert not ok, line


def test_a_row_at_exactly_L_and_one_byte_over():
    L = 128
    pad = L - len(b'{"level":"ERROR","m":""}')
    exact = b'{"level":"ERROR","m":"' + b"x" * pad + b'"}'
    over = b'{"level":"ERROR","m":"' + b"x" * (pad + 1) + b'"}'
    assert len(exact) == L and len(over) == L + 1
    status = check_rows([exact], L, [OK])
    assert status[0] == OK
    # one byte over the slot: the stage sees a row cut short and says so
    _plan, out = run_stage([over], L)
    assert out[3][0] == SHAPE and not out[0][0]
    # through the dispatcher such a row takes the next bucket and is ok
    status = check_rows([exact, over], 256, [OK, OK])


# -- seeded random rows --------------------------------------------------------------

_KEYS = ["time", "level", "service", "host", "pid", "msg", "ctx", "tags", "n",
         "ok", "é"]
_WORDS = ["ERROR", "WARN", "INFO", "DEBUG", "a b", "", "x{y}", "k:v,w", "[z]",
          "é", "q\"uote", "back\\slash", "\\\"", "tab\there", "plain"]


def _value(r: random.Random, depth: int = 0):
    kind = r.randrange(8 if depth < 2 else 5)
    if kind == 0:
        return r.choice(_WORDS)
    if kind == 1:
        return r.choice([0, -1, 7, 123456789012, 0.5, -2.25e-3, 1e9])
    if kind == 2:
        return r.choice([True, False, None])
    if kind in (3, 4):
        return r.choice(["ERROR", "WARN", "INFO"])
    if kind == 5:
        return {r.choice(_KEYS): _value(r, depth + 1)
                for _ in range(r.randrange(3))}
    return [_value(r, depth + 1) for _ in range(r.randrange(3))]


def _random_row(r: random.Random, L: int) -> bytes:
    keys = r.sample(_KEYS, r.randrange(0, 7))
    obj = {k: _value(r) for k in keys}
    if r.random() < 0.3:
        sep = (r.choice([", ", " , ", ","]), r.choice([": ", " : ", ":"]))
        text = json.dumps(obj, ensure_ascii=r.random() < 0.5, separators=sep)
    else:
        text = json.dumps(obj, ensure_ascii=False, separators=(",", ":"))
    line = text.encode("utf-8")
    roll = r.random()
    if roll < 0.08 and len(line) > 2:               # truncated
        line = line[:r.randrange(1, len(line))]
    elif roll < 0.12:                               # a byte changed
        k = r.randrange(len(line))
        line = line[:k] + bytes([r.choice(b'{}[]:,"\\ x1')]) + line[k + 1:]
    elif roll < 0.15:
        line = line + r.choice([b"}", b" x", b","])
    return line[:L]


@pytest.mark.parametrize("seed,L", [(s, L) for s in range(6)
                                    for L in (128, 256)])
def test_seeded_random_rows_agree_or_are_handed_back(seed, L):
    r = random.Random(seed * 1000 + L)
    lines = [_random_row(r, L) for _ in range(64)]
    status = check_rows(lines, L)
    # the generator makes rows of every outcome; most parse on the device
    assert (status == OK).sum() >= 16
    # a row json.loads takes that holds no backslash is ok unless it is
    # beyond what the stage states it proves (depth, members)
    for line, st in zip(lines, status):
        if reference(line) is not None and b"\\" not in line \
                and st != OK:
            assert st == SHAPE and (line.count(b"[") + line.count(b"{") > 3
                                    or b'"level"' in line), line


# -- the fused run --------------------------------------------------------------------

JSON_FILTER = {
    "inputs": [],
    "processors": [
        {"Type": "processor_parse_json_tpu"},
        {"Type": "processor_filter_native", "Include": {"level": INCLUDE}},
    ],
    "flushers": [{"Type": "flusher_stdout"}],
}


def json_counts():
    """The stage's counters now, flat (they last as long as the process)."""
    js = fp.stage_fusion_status().get("json") or {}
    out = {"rows": js.get("rows_total", 0),
           "decoded": js.get("signatures_decoded_total", 0)}
    out.update(js.get("host_rows_total")
               or dict.fromkeys(fp.JSON_HOST_REASONS, 0))
    return out


def since(before):
    return {k: v - before[k] for k, v in json_counts().items()}


def records(group):
    """The group's events as {field: bytes}."""
    cols = group.columns
    arena = group.source_buffer.as_array()
    out = []
    for i in range(len(cols)):
        rec = {}
        for k, (offs, lens) in cols.fields.items():
            if lens[i] >= 0:
                rec[k] = bytes(arena[int(offs[i]):int(offs[i]) + int(lens[i])])
        out.append(rec)
    return out


def test_planner_fuses_json_and_filter_into_one_run():
    p = build_pipeline(JSON_FILTER, "json-plan")
    assert len(p._fused_runs) == 1
    run = p._fused_runs[0]
    assert [m.spec.kind for m in run.members] == ["json_fields", "keep"]
    plan = run.members[0].spec.payload
    assert plan.bound == ["level"]
    cond = run.members[1].spec.payload[0]
    assert cond.kind == "span_match" and cond.binding == (0, plan.kmax)


def test_keep_mask_is_the_references_decision_on_every_ok_row():
    lines = [c[0] for c in CLASSES if len(c[0]) <= 256]
    p = build_pipeline(JSON_FILTER, "json-keep")
    program = p._fused_runs[0].program()
    d = fp.FusedDispatch(program, *_arena(lines)).dispatch()
    res = d.result()
    ok, keep = res.stages[0][0], res.stages[1][0]
    assert ok.sum() >= 10
    for line, o, k in zip(lines, ok, keep):
        if o:
            assert bool(k) == reference_keeps(line), line
        else:
            assert not k, line                   # says nothing of such a row


def _arena(lines):
    g = make_group(lines)
    cols = g.columns
    return g.source_buffer.as_array(), cols.offsets, cols.lengths


def test_fused_run_delivers_what_the_reference_keeps():
    r = random.Random(2701)
    lines = [c[0] for c in CLASSES if len(c[0]) <= 256] \
        + [_random_row(r, 256) for _ in range(200)]
    p = build_pipeline(JSON_FILTER, "json-run")
    before = json_counts()
    # process-lifetime counters: another test file on this worker may have
    # demoted chunks on purpose
    fusion_before = fp.stage_fusion_status()
    g = process_one(p, lines)
    want = [ln for ln in lines if reference_keeps(ln)]
    got = records(g)
    assert len(got) == len(want)
    for rec, line in zip(got, want):
        assert agrees(rec, reference(line)), line
    doc = fp.stage_fusion_status()
    assert doc["fused_dispatch_total"] > fusion_before["fused_dispatch_total"]
    assert doc["fused_demotions_total"] \
        == fusion_before["fused_demotions_total"]
    js = since(before)
    assert js["rows"] == len(lines)
    assert js["escape"] > 0 and js["shape"] > 0 and js["not_object"] > 0
    assert js["overlong"] == 0 and js["decoded"] >= 1


def test_fused_run_is_byte_identical_to_the_host_plane(monkeypatch):
    r = random.Random(2702)
    lines = [c[0] for c in CLASSES if len(c[0]) <= 256] \
        + [_random_row(r, 256) for _ in range(120)]
    cfg = dict(JSON_FILTER)
    cfg["processors"] = [JSON_FILTER["processors"][0]]     # the parse alone
    fused = records(process_one(build_pipeline(JSON_FILTER, "json-a"), lines))
    monkeypatch.setenv("LOONG_FUSED", "0")
    staged = records(process_one(build_pipeline(JSON_FILTER, "json-b"), lines))
    assert fused == staged
    # and rows that do not parse keep rawLog, as the host plane has it
    g = process_one(build_pipeline(cfg, "json-c"), lines)
    raw = [rec for rec in records(g) if "rawLog" in rec]
    assert len(raw) == sum(reference(ln) is None for ln in lines)


def test_key_names_are_decoded_once_per_signature():
    same = [b'{"time":"%d","level":"%s","n":%d}'
            % (i, [b"INFO", b"ERROR"][i % 2], i) for i in range(100)]
    p = build_pipeline(JSON_FILTER, "json-sig")
    before = json_counts()
    process_one(p, same)
    process_one(p, same)
    assert since(before) == {"rows": 200, "decoded": 1, "escape": 0,
                             "shape": 0, "not_object": 0, "overlong": 0}


def test_a_json_only_pipeline_keeps_the_host_plane():
    cfg = dict(JSON_FILTER)
    cfg["processors"] = [JSON_FILTER["processors"][0]]
    p = build_pipeline(cfg, "json-alone")
    assert p._fused_runs == []
    g = process_one(p, [b'{"level":"ERROR","n":1}'])
    assert records(g) == [{"level": b"ERROR", "n": b"1"}]
    assert fp.stage_fusion_status()["programs"] == []


def test_extract_keep_program_keeps_its_identity():
    """The regex → filter program's cache key is what it was before the
    json_fields stage existed (PERF_LEDGER's filter512.backlog finds its
    compiled program again)."""
    cfg = {"inputs": [], "flushers": [{"Type": "flusher_stdout"}],
           "processors": [
               {"Type": "processor_parse_regex_tpu",
                "Regex": r'(\S+) (\S+) (\S+) \[([^\]]+)\] "(\S+) (\S+) '
                         r'([^"]*)" (\d{3}) (\d+)',
                "Keys": ["ip", "ident", "user", "time", "method", "url",
                         "protocol", "status", "size"]},
               {"Type": "processor_filter_native",
                "Include": {"status": r"[45]\d\d"}}]}
    run = build_pipeline(cfg, "regex-filter")._fused_runs[0]
    assert fp.program_signature([m.spec for m in run.members]) \
        == "45566143dcbcc118b7bf"


def test_planner_refuses_a_span_match_bound_to_a_stage_without_spans(caplog):
    from loongcollector_tpu.pipeline.fused_chain import (FusedMemberStage,
                                                         _unbound_span)
    scan = FusedMemberStage(fp.StageSpec("scan", None, ["scan"]), None)
    cond = fp.StageCond("span_match", None, ["span_match"], binding=(0, 0))
    keep = fp.StageSpec("keep", [cond], ["keep"])
    why = _unbound_span(keep, [scan])
    assert why and "publishes no span columns" in why
    plan = jf.JsonFieldsPlan()
    spans = FusedMemberStage(fp.StageSpec("json_fields", plan, ["j"]), None)
    assert _unbound_span(keep, [spans]) is None
    cond.binding = (0, plan.kmax)             # a capture nobody bound
    assert "publishes 16" in _unbound_span(keep, [spans])


def test_an_overlong_group_takes_the_host_plane_and_is_counted():
    long_row = b'{"level":"ERROR","m":"' + b"x" * 5000 + b'"}'
    lines = [b'{"level":"WARN","n":1}', long_row, b'{"level":"INFO"}']
    p = build_pipeline(JSON_FILTER, "json-long")
    before = json_counts()
    got = records(process_one(p, lines))
    assert [r["level"] for r in got] == [b"WARN", b"ERROR"]
    assert since(before) == {"rows": 3, "decoded": 0, "escape": 0, "shape": 0,
                             "not_object": 0, "overlong": 3}


def test_host_emit_is_a_span_under_the_runs_complete():
    from loongcollector_tpu import trace
    lines = [b'{"level":"WARN","m":"a\\"b"}', b'{"level":"ERROR","m":"c"}',
             b'not json']
    p = build_pipeline(JSON_FILTER, "json-span")
    tracer = trace.enable()
    try:
        got = records(process_one(p, lines))
        spans, _events = tracer.drain()
    finally:
        trace.disable()
    assert [r["m"] for r in got] == [b'a"b', b"c"]
    by_id = {s.span_id: s for s in spans}
    emit = [s for s in spans if s.name == "json.host_emit"]
    assert len(emit) == 1 and emit[0].attrs["rows"] == 2
    assert by_id[emit[0].parent_id].name == "processor.fused_chain.complete"
