"""processor_classify_url_tpu's rule list as ONE walk of one merged automaton
(ISSUE 38): the ``label`` kernel, the fused ``extract → label`` run, the
unfused ``process()`` and a twenty-line ``re`` reference agree row for row —
on seeded random paths and on the hard ones (several rules match: the lowest
wins, and a permuted list changes the label as the reference says; no rule;
an empty path; an absent path; a path at the bucket's width; bytes ≥ 0x80; a
group of one row).  The benchmark cell's own list fuses ``device_ok`` with
the tiers its configuration states; a list the device automaton cannot hold
refuses fusion and gives the same answers on the host tier; an injected
``device_plane.fused_dispatch`` fault demotes the chunk to ``staged`` with
the same answers; the counters add up."""

import os
import random
import re

import numpy as np
import pytest
import yaml

from loongcollector_tpu import chaos, models
from loongcollector_tpu.chaos import ChaosPlan, FaultSpec
from loongcollector_tpu.models import (ColumnarLogs, PipelineEventGroup,
                                       SourceBuffer)
from loongcollector_tpu.monitor import exposition
from loongcollector_tpu.ops import device_stream
from loongcollector_tpu.ops import fused_pipeline as fp
from loongcollector_tpu.ops.device_batch import pack_rows
from loongcollector_tpu.ops.device_plane import DevicePlane
from loongcollector_tpu.ops.kernels.dfa_scan import (build_span_label_fn,
                                                     first_pattern)
from loongcollector_tpu.ops.regex.fuse import (DEVICE_MAX_STATES,
                                               MAX_PATTERNS, compile_fused)
from loongcollector_tpu.ops.regex.program import PatternTier
from loongcollector_tpu.pipeline.pipeline import CollectionPipeline
from loongcollector_tpu.pipeline.plugin.interface import PluginContext
from loongcollector_tpu.processor import classify_url
from loongcollector_tpu.processor.classify_url import ProcessorClassifyUrl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_YAML = os.path.join(ROOT, "perfbench", "configs",
                         "file_http_classify_url", "pipeline.yaml")
PARSE = r"(\d+) (\S+) (\S*)"
KEYS = ["id", "method", "path"]


def cell_rules():
    """The rule list of the benchmark cell, read out of its pipeline.yaml."""
    text = open(CELL_YAML).read().replace("{log_path}", "/x").replace(
        "{sink_path}", "/y")
    procs = yaml.safe_load(text)["processors"]
    assert [p["Type"] for p in procs] == ["processor_parse_regex_tpu",
                                          "processor_classify_url_tpu"]
    return [(r["Name"], r["Regex"]) for r in procs[1]["Rules"]]


RULES = cell_rules()


def reference(rules, path, default=b"other"):
    """The category ``re`` gives: the first rule that fully matches, else
    the default; None for a row without the field."""
    if path is None:
        return None
    for name, rx in rules:
        if re.fullmatch(rx.encode("latin-1"), path) is not None:
            return name.encode()
    return default


@pytest.fixture(autouse=True)
def _fused_env(monkeypatch):
    """Fusion and the device path forced on (a CPU backend would keep both
    off), fresh device plane / ring / program cache / counters per test."""
    monkeypatch.setenv("LOONG_FUSED", "1")
    prev = models.set_columnar_enabled(True)
    DevicePlane.reset_for_testing()
    device_stream.reset_for_testing()
    fp.reset_for_testing()
    classify_url.reset_for_testing()
    yield
    models.set_columnar_enabled(prev)
    DevicePlane.reset_for_testing()
    device_stream.reset_for_testing()
    fp.reset_for_testing()
    classify_url.reset_for_testing()


def make_group(lines):
    blob = b"".join(lines)
    sb = SourceBuffer(len(blob) + 256)
    g = PipelineEventGroup(sb)
    views = [sb.copy_string(ln) for ln in lines]
    g.set_columns(ColumnarLogs(
        offsets=np.array([v.offset for v in views], np.int32),
        lengths=np.array([len(ln) for ln in lines], np.int32),
        timestamps=np.full(len(lines), 1700000002, np.int64)))
    return g


def pipeline_config(rules, source_key="path", parse=True):
    procs = [{"Type": "processor_parse_regex_tpu", "Regex": PARSE,
              "Keys": KEYS}] if parse else []
    procs.append({"Type": "processor_classify_url_tpu",
                  "SourceKey": source_key, "TargetKey": "category",
                  "DefaultCategory": "other",
                  "Rules": [{"Name": n, "Regex": rx} for n, rx in rules]})
    return {"inputs": [], "processors": procs,
            "flushers": [{"Type": "flusher_stdout"}]}


def build(rules, name, **kw):
    p = CollectionPipeline()
    assert p.init(name, pipeline_config(rules, **kw))
    return p


def run(pipeline, lines):
    g = make_group(lines)
    fin = pipeline.process_begin([g])
    while fin is not None:
        fin = fin()
    return g


def field(group, name):
    """The named field of every row as bytes, None where absent."""
    offs, lens = group.columns.fields[name]
    arena = group.source_buffer.as_array()
    return [None if ln < 0 else arena[o:o + ln].tobytes()
            for o, ln in zip(offs.tolist(), lens.tolist())]


def random_path(r):
    kind = r.randrange(12)
    v = f"/api/v{r.randrange(1, 12)}"
    q = "?" + "".join(r.choice("abc=&123/.?") for _ in range(r.randrange(0, 9))) \
        if r.random() < 0.5 else ""
    if kind == 0:
        return r.choice(["/healthz", "/readyz", "/livez", "/metrics",
                         "/healthz/", "/metric"])
    if kind == 1:
        return f"{v}/users/{r.randrange(10**r.randrange(1, 6))}/orders" + \
            (f"/{r.randrange(999)}" if r.random() < 0.5 else "") + q
    if kind == 2:
        return f"{v}/users/{r.randrange(10**r.randrange(1, 6))}" + q
    if kind == 3:
        return f"{v}/orders/{r.randrange(10**r.randrange(1, 6))}" + q
    if kind == 4:
        return f"{v}/search" + q
    if kind == 5:
        return r.choice(["/login", "/logout", "/oauth/token", "/oauth"]) + q
    if kind == 6:
        return f"{v}/" + "".join(r.choice("abc_xyzU9")
                                 for _ in range(r.randrange(0, 8))) + \
            ("/" + "".join(r.choice("ab/1.") for _ in range(r.randrange(5)))
             if r.random() < 0.5 else "") + q
    if kind == 7:
        return "/static/" + "".join(r.choice("ab/.1")
                                    for _ in range(r.randrange(0, 9))) + \
            r.choice([".js", ".css", ".png", ".svg", ".woff2", ".map",
                      ".jsx", "js"]) + q
    if kind == 8:
        return r.choice(["/", "/favicon.ico", "/wp-login.php", "/.env", ""])
    return "".join(r.choice("/apiv1users?=.") for _ in range(r.randrange(0, 30)))


def random_lines(seed, n):
    r = random.Random(seed)
    lines, paths = [], []
    for i in range(n):
        if r.random() < 0.05:                     # the parse rejects the line
            lines.append(b"x%d GET /healthz" % i)
            paths.append(None)
            continue
        path = random_path(r).encode()
        lines.append(b"%d %s %s" % (i, r.choice([b"GET", b"POST"]), path))
        paths.append(path)
    return lines, paths


HARD = [
    # (path, the category the cell's list gives)
    (b"/api/v1/users/12/orders/7?x=1", b"user_orders"),   # also api_other
    (b"/api/v1/users/12/orders", b"user_orders"),
    (b"/api/v2/users/12", b"user"),                        # also api_other
    (b"/api/v3/orders/9?a", b"order"),                     # also api_other
    (b"/api/v1/search?q=tpu", b"search"),                  # also api_other
    (b"/api/v1/search", b"api_other"),                     # search needs a ?
    (b"/api/v1/users/12/ordersX", b"api_other"),
    (b"/healthz", b"health"), (b"/healthz?x", b"other"),
    (b"/oauth/token?grant=x", b"auth"),
    (b"/static/a/b.min.js?v=3", b"static"), (b"/static/app.js.map", b"other"),
    (b"/", b"other"), (b"/.env", b"other"), (b"", b"other"),   # an empty path
    (b"/api/v1/caf\xc3\xa9", b"other"),                    # bytes >= 0x80
    (b"/static/\xff\xfe.png", b"static"),
]


# -- the list itself ------------------------------------------------------------------

def test_the_cells_list_fuses_device_ok_with_the_tiers_its_table_states():
    assert [n for n, _ in RULES] == ["health", "user_orders", "user", "order",
                                     "search", "auth", "api_other", "static"]
    proc = ProcessorClassifyUrl()
    assert proc.init(pipeline_config(RULES)["processors"][1], PluginContext())
    tiers = [e.tier for _, e in proc.rules]
    assert tiers == [PatternTier.SEGMENT] * 7 + [PatternTier.DFA]
    fdfa = proc._set.fdfa
    assert proc._set.n_fused == 8 and fdfa.device_ok
    assert fdfa.num_states <= DEVICE_MAX_STATES
    # the budget is why the list has eight (ISSUE 38 read 102 x 31)
    assert (fdfa.num_states, fdfa.num_classes) == (102, 31)
    # rules 2-5 also fully match rule 7: the list's order shows
    for path in (b"/api/v1/users/1/orders", b"/api/v1/users/1",
                 b"/api/v1/orders/1", b"/api/v1/search?q"):
        assert re.fullmatch(RULES[6][1].encode(), path)


def test_first_pattern_is_the_lowest_set_bit():
    tags = np.array([0, 1, 2, 3, 4, 6, 0x80, 0x80000000, 0xC0000000, 0x50],
                    dtype=np.uint32)
    assert first_pattern(tags).tolist() == [-1, 0, 1, 0, 2, 1, 7, 31, 30, 4]


# -- the kernel -----------------------------------------------------------------------

def _kernel_labels(rules, values, L=128, starts=None):
    """The label kernel over ``values`` packed one a row (at ``starts``
    inside a row of filler bytes where given); None marks an absent span."""
    import jax
    fdfa = compile_fused([rx for _, rx in rules])
    assert fdfa.device_ok
    fn = jax.jit(build_span_label_fn(fdfa))
    n = len(values)
    rows = np.full((n, L), ord("/"), dtype=np.uint8)       # live filler
    st = np.zeros(n, np.int32) if starts is None else np.asarray(starts,
                                                                 np.int32)
    ln = np.array([-1 if v is None else len(v) for v in values], np.int32)
    for i, v in enumerate(values):
        if v:
            rows[i, st[i]:st[i] + len(v)] = np.frombuffer(v, np.uint8)
    lengths = np.full(n, L, np.int32)
    return np.asarray(fn(rows, lengths, st, ln)).tolist()


def test_kernel_labels_the_hard_paths_as_re_does():
    names = [n.encode() for n, _ in RULES]
    paths = [p for p, _ in HARD] + [None]
    got = _kernel_labels(RULES, paths,
                         starts=[(3 * i) % 40 for i in range(len(paths))])
    for (path, want), k in zip(HARD, got):
        assert (names[k] if k >= 0 else b"other") == want == \
            reference(RULES, path), path
    assert got[-1] == -1                                   # the absent span


def test_kernel_agrees_with_re_on_random_paths_and_a_permuted_list():
    lines, paths = random_lines(38, 600)
    paths = [p for p in paths if p is not None][:512]
    starts = [(7 * i) % (128 - len(p) + 1) for i, p in enumerate(paths)]
    for rules in (RULES, RULES[6:] + RULES[:6]):
        names = [n.encode() for n, _ in rules]
        got = _kernel_labels(rules, paths, starts=starts)
        want = [reference(rules, p) for p in paths]
        assert [names[k] if k >= 0 else b"other" for k in got] == want
    # the permutation moved the specific routes under api_other
    assert reference(RULES[6:] + RULES[:6], b"/api/v1/users/12") == b"api_other"


def test_kernel_takes_a_path_at_the_buckets_width_and_one_row():
    wide = b"/api/v1/orders/1?" + b"q" * (128 - 17)
    assert len(wide) == 128
    assert _kernel_labels(RULES, [wide]) == [3]
    assert _kernel_labels(RULES, [None]) == [-1]
    assert _kernel_labels(RULES, [b""]) == [-1]           # no rule


# -- the fused run, the unfused run and re ---------------------------------------------

def _both_ways(rules, lines, monkeypatch, name):
    """The category column of ``lines`` through the fused run and through
    the per-stage path, and the fused run's program."""
    fused = build(rules, name + "-f")
    assert [(r.head, r.end) for r in fused._fused_runs] == [(0, 2)]
    g1 = run(fused, lines)
    program = fused._fused_runs[0].program()
    monkeypatch.setenv("LOONG_FUSED", "0")
    g2 = run(build(rules, name + "-s"), lines)
    monkeypatch.setenv("LOONG_FUSED", "1")
    assert field(g1, "path") == field(g2, "path")
    return field(g1, "category"), field(g2, "category"), program


def test_fused_run_unfused_run_and_re_agree_row_for_row(monkeypatch):
    lines, paths = random_lines(2147483659, 700)
    lines += [b"%d GET %s" % (9000 + i, p) for i, (p, _) in enumerate(HARD)]
    paths += [p for p, _ in HARD]
    plane = DevicePlane.instance()
    fused, staged, program = _both_ways(RULES, lines, monkeypatch, "cu-a")
    want = [reference(RULES, p) for p in paths]
    assert fused == want and staged == want
    assert None in want and b"other" in want and b"user_orders" in want
    # one program a group: extract and label in ONE dispatch
    assert program.dispatch_count == 1
    assert [s.kind for s in program.specs] == ["extract", "label"]
    assert program.specs[1].binding == (0, 2)
    doc = program.status()
    assert doc["stages"][1].startswith("label:") and doc["captures"] == [3, 0]
    assert plane.dispatched_total() >= 1


def test_a_permuted_list_changes_the_label_as_the_reference_says(monkeypatch):
    lines, paths = random_lines(77, 300)
    permuted = RULES[6:] + RULES[:6]
    fused, staged, _ = _both_ways(permuted, lines, monkeypatch, "cu-p")
    want = [reference(permuted, p) for p in paths]
    assert fused == want == staged
    assert want != [reference(RULES, p) for p in paths]


def test_a_group_of_one_row_and_a_group_with_no_parsed_row(monkeypatch):
    for lines, want in (([b"1 GET /api/v1/orders/5"], [b"order"]),
                        ([b"bad line"], [None]),
                        ([b"bad", b"2 GET "], [None, b"other"])):
        fused, staged, _ = _both_ways(RULES, lines, monkeypatch,
                                      "cu-1-%d" % len(lines[0]))
        assert fused == want == staged


def test_counters_add_up_and_say_where_rows_went(monkeypatch):
    lines, paths = random_lines(5, 400)
    p = build(RULES, "cu-count")
    run(p, lines)
    monkeypatch.setenv("LOONG_FUSED", "0")
    run(p, lines)
    doc = exposition.collect_status()["classify_url"]["cu-count"]
    absent = sum(p is None for p in paths)
    want = [reference(RULES, p) for p in paths]
    assert doc["rows_total"] == 800
    assert doc["label_program_rows_total"] == 400 - absent
    assert doc["host_rows_total"] == 400 - absent
    assert doc["absent_rows_total"] == 2 * absent
    assert doc["rows_total"] == doc["label_program_rows_total"] \
        + doc["host_rows_total"] + doc["absent_rows_total"]
    assert doc["default_rows_total"] == 2 * want.count(b"other")
    assert doc["rule_rows_total"] == [2 * want.count(n.encode())
                                      for n, _ in RULES]
    assert "classify_url" in exposition.STATUS_SECTIONS


# -- lists the device automaton cannot hold --------------------------------------------

def _refusing_lists():
    backref = RULES[:3] + [("twice", r"/(a+)/\1")] + RULES[3:6]
    wide = [(f"r{i}", "/" + "".join(chr(97 + (i * 7 + j) % 26) for j in range(9))
             + r"\d+/x")
            for i in range(20)]
    many = [(f"m{i}", f"/m{i}(/.*)?") for i in range(MAX_PATTERNS + 2)]
    return [("cpu_tier_rule", backref), ("over_device_states", wide),
            ("over_max_patterns", many)]


@pytest.mark.parametrize("what,rules", _refusing_lists(),
                         ids=[w for w, _ in _refusing_lists()])
def test_a_list_the_device_cannot_hold_refuses_fusion_same_answers(what,
                                                                   rules):
    proc = ProcessorClassifyUrl()
    assert proc.init(pipeline_config(rules)["processors"][1], PluginContext())
    fs = proc._set
    if what == "cpu_tier_rule":
        assert any(e.tier is PatternTier.CPU for _, e in proc.rules)
        assert fs is None                   # the automaton must hold EVERY rule
    elif what == "over_device_states":
        assert fs is not None and fs.fdfa.num_states > DEVICE_MAX_STATES \
            and not fs.fdfa.device_ok
    else:
        assert len(rules) > MAX_PATTERNS and fs is None
    p = build(rules, "cu-" + what)
    assert p._fused_runs == []              # the run ends before the classifier
    r = random.Random(11)
    paths = [b"/aa/aa", b"/a/aa", b"/m3/x", b"/m33", b"/api/v1/users/3",
             b"/healthz", b"/abcdefghi7/x", b"/hovcjqxel12/x", b""] + \
        [random_path(r).encode() for _ in range(200)]
    lines = [b"%d GET %s" % (i, p_) for i, p_ in enumerate(paths)] + [b"bad"]
    g = run(p, lines)
    assert field(g, "category") == [reference(rules, p_) for p_ in paths] \
        + [None]
    doc = classify_url.status()["cu-" + what]
    assert doc["label_program_rows_total"] == 0
    assert doc["host_rows_total"] == len(paths)


def test_a_lone_classifier_and_one_on_an_unknown_field_keep_the_host_tier():
    lone = build(RULES, "cu-lone", parse=False, source_key="content")
    assert lone._fused_runs == []
    g = run(lone, [b"/healthz", b"/api/v1/users/1", b"/nope"])
    assert field(g, "category") == [b"health", b"user", b"other"]
    stray = build(RULES, "cu-stray", source_key="uri")
    assert stray._fused_runs == []
    g = run(stray, [b"1 GET /healthz"])
    assert "category" not in g.columns.fields


def test_behind_a_json_stage_the_rows_it_left_to_the_host_are_decided_there():
    """A ``json_fields`` producer mints the capture the rule list binds; the
    rows whose spans the device did not produce (an escape in the line: the
    host's emitter installs them) are labelled by the host's scanner."""
    cfg = pipeline_config(RULES)
    cfg["processors"][0] = {"Type": "processor_parse_json_tpu"}
    p = CollectionPipeline()
    assert p.init("cu-json", cfg)
    assert [[m.spec.kind for m in r.members] for r in p._fused_runs] \
        == [["json_fields", "label"]]
    paths = [b"/api/v1/users/5?a=1", b"/healthz", b"/nope", b"/api/v2/cart",
             b"/static/a.css", b"/api/v1/orders/8"]
    lines = [b'{"id":"%d","path":"%s","note":"%s"}'
             % (i, path, b'a\\"b' if i % 2 else b"ab")
             for i, path in enumerate(paths)]
    lines.append(b'{"id":"9","other":"x"}')           # no path member
    g = run(p, lines)
    assert field(g, "category") == [reference(RULES, x) for x in paths] \
        + [None]
    doc = classify_url.status()["cu-json"]
    assert doc["host_rows_total"] == 3 and doc["absent_rows_total"] == 1
    assert doc["label_program_rows_total"] == 3 and doc["rows_total"] == 7


# -- a demoted chunk --------------------------------------------------------------------

def test_an_injected_fused_dispatch_fault_demotes_to_staged_same_answers():
    lines, paths = random_lines(91, 500)
    p = build(RULES, "cu-chaos")
    program = p._fused_runs[0].program()
    chaos.install(ChaosPlan(5, {
        "device_plane.fused_dispatch": FaultSpec(
            prob=1.0, kinds=(chaos.ACTION_ERROR,), max_faults=1)}))
    try:
        g = run(p, lines)
    finally:
        chaos.uninstall()
    assert program.demotions == 1
    assert field(g, "category") == [reference(RULES, p_) for p_ in paths]
    # the next group rides the program again
    g = run(p, lines)
    assert program.demotions == 1
    assert field(g, "category") == [reference(RULES, p_) for p_ in paths]


def test_the_staged_label_is_the_kernels_twin():
    proc = ProcessorClassifyUrl()
    assert proc.init(pipeline_config(RULES)["processors"][1], PluginContext())
    paths = [p for p, _ in HARD]
    arena = np.frombuffer(b"".join(paths) or b"\0", np.uint8)
    lens = np.array([len(p) for p in paths], np.int32)
    offs = np.cumsum(lens) - lens
    batch = pack_rows(arena, offs.astype(np.int64), lens, 128)
    n = len(paths)
    spanlens = np.concatenate([lens, np.full(batch.rows.shape[0] - n, -1,
                                             np.int32)])
    starts = np.zeros(len(spanlens), np.int32)
    got = proc._staged_label(batch.rows, batch.lengths, starts, spanlens)
    assert got[:n].tolist() == _kernel_labels(RULES, paths)
    assert (got[n:] == -1).all()
