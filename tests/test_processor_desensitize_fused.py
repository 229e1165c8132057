"""processor_desensitize_native with upstream's keys: the fused ``mask`` stage
(the find-all walk inside the ``extract → mask`` program, the native rebuild
of the group's values), the host tier and ``re.sub`` agree row for row — on
seeded random URLs and on the hard ones: adjacent matches, a greedy content
that swallows a later prefix, empty content, a match at the span's first and
last byte, a match that must stop at the span's end, more matches than the
stage's slots, an absent span, ``ReplacingAll: false``, ``md5`` (host tier),
bytes >= 0x80 (host tier), a prefix of fixed-length byte classes, a lone
desensitizer with no span-publishing stage ahead of it, and a demoted chunk.
The rule for which pairs the device takes, the kernel against ``re`` on its
own, the native rebuild against its Python twin, and the counters."""

import hashlib
import random
import re

import numpy as np
import pytest

from loongcollector_tpu import chaos, models, native
from loongcollector_tpu.chaos import ChaosPlan, FaultSpec
from loongcollector_tpu.models import (ColumnarLogs, PipelineEventGroup,
                                       SourceBuffer)
from loongcollector_tpu.monitor import exposition
from loongcollector_tpu.ops import device_stream
from loongcollector_tpu.ops import fused_pipeline as fp
from loongcollector_tpu.ops.device_plane import DevicePlane
from loongcollector_tpu.ops.kernels.dfa_scan import (MASK_ABSENT,
                                                     MASK_NON_ASCII,
                                                     build_span_mask_fn)
from loongcollector_tpu.ops.regex.fuse import try_build_mask
from loongcollector_tpu.pipeline.pipeline import CollectionPipeline
from loongcollector_tpu.pipeline.plugin.interface import PluginContext
from loongcollector_tpu.processor import desensitize
from loongcollector_tpu.processor.desensitize import (SLOTS,
                                                      ProcessorDesensitize)

PARSE = r"(\d+) (\S+) (\S*) (\S+)"
KEYS = ["id", "method", "url", "proto"]
BEFORE = "(?:AccessKeyId|Signature|SecurityToken)="
CONTENT = "[^&]+"
STARS = b"********"


@pytest.fixture(autouse=True)
def _fused_env(monkeypatch):
    """Fusion and the device path forced on (a CPU backend would keep both
    off), fresh device plane / ring / program cache / counters per test."""
    monkeypatch.setenv("LOONG_FUSED", "1")
    prev = models.set_columnar_enabled(True)
    DevicePlane.reset_for_testing()
    device_stream.reset_for_testing()
    fp.reset_for_testing()
    desensitize.reset_for_testing()
    yield
    models.set_columnar_enabled(prev)
    DevicePlane.reset_for_testing()
    device_stream.reset_for_testing()
    fp.reset_for_testing()
    desensitize.reset_for_testing()


def reference(url, before=BEFORE, content=CONTENT, repl=STARS, count=0,
              md5=False):
    """What ``re`` gives: upstream's pattern over the value; None for a row
    without the field."""
    if url is None:
        return None
    rx = re.compile(b"(" + before.encode() + b")" + content.encode())

    def one(m):
        if md5:
            return m.group(1) + hashlib.md5(
                m.string[m.end(1):m.end()]).hexdigest().encode()
        return m.group(1) + repl
    return rx.sub(one, url, count=count)


def masker(before=BEFORE, content=CONTENT, **kw):
    return dict({"Type": "processor_desensitize_native", "SourceKey": "url",
                 "Method": "const", "ReplacingString": STARS.decode(),
                 "ContentPatternBeforeReplacedString": before,
                 "ReplacedContentPattern": content, "ReplacingAll": True},
                **kw)


def build(name, parse=True, **kw):
    procs = [{"Type": "processor_parse_regex_tpu", "Regex": PARSE,
              "Keys": KEYS}] if parse else []
    procs.append(masker(**kw))
    p = CollectionPipeline()
    assert p.init(name, {"inputs": [], "processors": procs,
                         "flushers": [{"Type": "flusher_stdout"}]})
    return p


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


def lines_of(urls):
    """One parsable line a URL (None: a line the parse rejects); the URL is
    followed by more bytes of the row, which no match may reach into."""
    return [b"x%d GET /rejected HTTP/1.1" % i if u is None
            else b"%d GET %s HTTP/1.1&Signature=tail" % (i, u)
            for i, u in enumerate(urls)]


_TOKENS = [b"AccessKeyId=", b"Signature=", b"SecurityToken=", b"&", b"&&",
           b"x", b"LTAIab12", b"q=1", b"%2F", b"Sig", b"=",
           b"XAccessKeyId=", b"%26Signature%3D", b"?", b"/api/v1/r"]


def random_urls(seed, n):
    r = random.Random(seed)
    out = []
    for _ in range(n):
        if r.random() < 0.04:
            out.append(None)
            continue
        url = b"/api/v1/resource/%d?" % r.randrange(10 ** 6)
        for _ in range(r.randrange(0, 9)):
            url += r.choice(_TOKENS)
        out.append(url)
    return out


HARD = [
    b"/a?AccessKeyId=LTAI1&Signature=xyz%3D&q=1",       # two, then a plain one
    b"/a?AccessKeyId=abcSignature=xyz",                  # greedy: one match
    b"/a?Signature=&AccessKeyId=k",                      # empty content
    b"Signature=abc",                                    # the whole span
    b"/a?q=1&SecurityToken=tok",                         # runs to the end
    b"/a?XAccessKeyId=v&ref=%26Signature%3Dw",           # look-alike; encoded
    b"/a?AccessKeyId=1&AccessKeyId=2&AccessKeyId=3&AccessKeyId=4",   # S
    b"/a?AccessKeyId=1&AccessKeyId=2&AccessKeyId=3&AccessKeyId=4&Signature=5",
    b"", b"/plain?q=1&r=2", b"&&&", b"Signature=",
]


# -- the pairs the device takes ----------------------------------------------------

@pytest.mark.parametrize("before,content,takes", [
    (BEFORE, CONTENT, [12, 10, 14]),
    ("[Pp]assword=", "[^&]*", [9]),
    (r"k\d{3}=", "x{2,5}", [5]),
    ("(?:ab|c)(?:d|ef)", "[^&]+", [3, 4, 2, 3]),       # re's order of trying
    ("key=", "(a|b)+", [4]),                           # one byte class still
    ("key=", ".", [4]),
    ("a+=", "x+", None),                               # no fixed length
    ("(?:Sig(?:nature)?)=", "x+", None),
    ("(?i)key=", "x+", None),                          # a flag
    ("", "x+", None),                                  # an empty prefix
    ("k=", "x+?", None),                               # lazy
    ("k=", "[^&]+|y", None),                           # not one class
    ("k=", "(?:ab)+", None),
])
def test_the_device_takes_fixed_length_prefixes_and_one_greedy_class(
        before, content, takes):
    plan = try_build_mask(before.encode(), content.encode(), SLOTS)
    if takes is None:
        assert plan is None
    else:
        assert [len(a) for a in plan.alternatives] == takes
        assert plan.slots == SLOTS and plan.replace_all
    first = try_build_mask(b"k=", b"x+", SLOTS, replace_all=False)
    assert first.slots == 1 and not first.replace_all


# -- the kernel ---------------------------------------------------------------------

def _kernel(before, content, values, slots=SLOTS, replace_all=True, L=96,
            starts=None):
    """The mask kernel over ``values`` packed one a row inside live filler
    (at ``starts``); None marks an absent span."""
    import jax
    plan = try_build_mask(before.encode(), content.encode(), slots,
                          replace_all)
    fn = jax.jit(build_span_mask_fn(plan))
    n = len(values)
    rows = np.full((n, L), ord("a"), dtype=np.uint8)
    st = np.zeros(n, np.int32) if starts is None else np.asarray(starts,
                                                                 np.int32)
    ln = np.array([-1 if v is None else len(v) for v in values], np.int32)
    for i, v in enumerate(values):
        if v:
            rows[i, st[i]:st[i] + len(v)] = np.frombuffer(v, np.uint8)
    count, first, last = (np.asarray(a) for a in fn(
        rows, np.full(n, L, np.int32), st, ln))
    return count, first - st[:, None], last - st[:, None]


def _pairs(before, content, value, count=0):
    rx = re.compile(b"(" + before.encode() + b")" + content.encode())
    got = [(m.end(1), m.end()) for m in rx.finditer(value)]
    return got[:1] if count == 1 else got


@pytest.mark.parametrize("before,content", [
    (BEFORE, CONTENT), (BEFORE, "[^&]*"), ("(?:Sig|Signature)=", "[^&]{1,3}"),
    ("[Ss]ig(?:nature|NATURE)=", "[a-z]+"), ("k=", "[0-9]+")])
def test_kernel_agrees_with_re_on_random_spans(before, content):
    r = random.Random(43)
    values, starts = [], []
    toks = _TOKENS + [b"k=1", b"k=22", b"abc", b"SigNATURE="]
    for _ in range(400):
        v = b"".join(r.choice(toks) for _ in range(r.randrange(0, 8)))[:60]
        values.append(v)
        starts.append(r.randrange(0, 96 - len(v) + 1))
    count, first, last = _kernel(before, content, values, starts=starts)
    for i, v in enumerate(values):
        want = _pairs(before, content, v)
        if len(want) > SLOTS:
            assert count[i] == SLOTS + 1, v
            continue
        got = [(int(first[i, k]), int(last[i, k])) for k in range(count[i])]
        assert got == want, v


def test_kernel_on_the_hard_spans_and_the_row_kinds_it_leaves_alone():
    values = HARD + [None, b"/a?Signature=caf\xc3\xa9"]
    count, first, last = _kernel(BEFORE, CONTENT, values,
                                 starts=[(5 * i) % 20 for i in range(
                                     len(values))])
    for i, v in enumerate(HARD):
        want = _pairs(BEFORE, CONTENT, v)
        if len(want) > SLOTS:
            assert count[i] == SLOTS + 1
            continue
        assert [(int(first[i, k]), int(last[i, k]))
                for k in range(count[i])] == want, v
    assert count[len(HARD)] == MASK_ABSENT
    assert count[len(HARD) + 1] == MASK_NON_ASCII
    # adjacent matches, and the first alone where ReplacingAll is false
    count, first, last = _kernel("k=", "[0-9]+", [b"k=1k=22k=333"])
    assert count.tolist() == [3] and first[0, :3].tolist() == [2, 5, 9]
    assert last[0, :3].tolist() == [3, 7, 12]
    count, first, last = _kernel("k=", "[0-9]+", [b"k=1k=22k=333"],
                                 replace_all=False)
    assert count.tolist() == [1] and first.shape == (1, 1)
    # the filler after the span is in the content class: the span ends it
    count, first, last = _kernel("k=", "[a-z0-9]+", [b"zk=12"])
    assert count.tolist() == [1] and last[0, 0] == 5


# -- the fused run, the host tier and re --------------------------------------------

def _both_ways(urls, monkeypatch, name, **kw):
    """The url column through the fused run and through the host tier (the
    per-stage path), and the fused run's program."""
    fused = build(name + "-f", **kw)
    assert [(r.head, r.end) for r in fused._fused_runs] == [(0, 2)]
    g1 = run(fused, lines_of(urls))
    program = fused._fused_runs[0].program()
    monkeypatch.setenv("LOONG_FUSED", "0")
    g2 = run(build(name + "-s", **kw), lines_of(urls))
    monkeypatch.setenv("LOONG_FUSED", "1")
    assert field(g1, "proto") == field(g2, "proto")
    return field(g1, "url"), field(g2, "url"), program


def test_fused_run_host_tier_and_re_agree_row_for_row(monkeypatch):
    urls = random_urls(2147483659, 700) + HARD
    plane = DevicePlane.instance()
    fused, host, program = _both_ways(urls, monkeypatch, "ds-a")
    want = [reference(u) for u in urls]
    assert fused == want and host == want
    assert None in want and any(w and STARS in w for w in want)
    # one program a group: extract and mask in ONE dispatch
    assert program.dispatch_count == 1
    assert [s.kind for s in program.specs] == ["extract", "mask"]
    assert program.specs[1].binding == (0, 2)
    doc = program.status()
    assert doc["stages"][1].startswith("mask:")
    assert doc["captures"] == [4, 0] and doc["slots"] == [0, SLOTS]
    util = plane.utilization()
    assert util["arrays_dispatched_total"] == util["dispatched_total"] >= 1
    assert util["h2d_arrays_total"] == util["arrays_dispatched_total"]


@pytest.mark.parametrize("kw,ref_kw", [
    ({"ReplacingAll": False}, {"count": 1}),
    ({"before": "[Pp]assword=", "content": "[^&]*"},
     {"before": "[Pp]assword=", "content": "[^&]*"}),
    ({"ReplacingString": "<masked>"}, {"repl": b"<masked>"}),
], ids=["first_only", "class_prefix_empty_content", "other_string"])
def test_the_pair_variants_agree_with_re(monkeypatch, kw, ref_kw):
    urls = random_urls(91, 300) + HARD + [
        b"/l?password=&Password=x&passwordX=y", b"password=abc"]
    fused, host, _ = _both_ways(urls, monkeypatch, "ds-" + "-".join(kw),
                                **kw)
    want = [reference(u, **ref_kw) for u in urls]
    assert fused == want == host


def test_md5_and_a_pair_without_device_form_keep_the_host_tier():
    urls = random_urls(5, 120) + HARD
    for kw, ref_kw in (({"Method": "md5"}, {"md5": True}),
                       ({"before": "Sig(?:nature)?="},
                        {"before": "Sig(?:nature)?="})):
        p = build("ds-host-" + kw.get("Method", "v"), **kw)
        assert p._fused_runs == []          # the run ends before the mask
        g = run(p, lines_of(urls))
        assert field(g, "url") == [reference(u, **ref_kw) for u in urls]
    doc = desensitize.status()["ds-host-md5"]
    present = sum(u is not None for u in urls)
    assert doc["host_rows_total"]["tier"] == present
    assert doc["mask_program_rows_total"] == 0


def test_rows_the_stage_leaves_to_the_host_are_decided_there():
    urls = [b"/a?AccessKeyId=1&Signature=2", b"/a?Signature=caf\xc3\xa9&q=1",
            HARD[7], None, b"/b?q=1", HARD[6]]
    p = build("ds-left")
    g = run(p, lines_of(urls))
    assert field(g, "url") == [reference(u) for u in urls]
    doc = desensitize.status()["ds-left"]
    assert doc["host_rows_total"] == dict(
        dict.fromkeys(desensitize.HOST_REASONS, 0), non_ascii=1, over_slots=1)
    assert doc["mask_program_rows_total"] == 3 and doc["absent_rows_total"] == 1
    assert doc["rows_total"] == 6 and doc["masked_rows_total"] == 4
    assert doc["matches_total"] == 2 + 1 + 5 + 4


def test_a_lone_desensitizer_keeps_the_host_tier_on_the_raw_content():
    lone = build("ds-lone", parse=False)
    assert lone._fused_runs == []
    lone_cfg = masker()
    lone_cfg["SourceKey"] = "content"
    p = CollectionPipeline()
    assert p.init("ds-lone-c", {"inputs": [], "processors": [lone_cfg],
                                "flushers": [{"Type": "flusher_stdout"}]})
    lines = [b"GET /a?AccessKeyId=1&Signature=2", b"nothing here"]
    g = run(p, lines)
    got = [g.source_buffer.as_array()[o:o + n].tobytes() for o, n in zip(
        g.columns.offsets.tolist(), g.columns.lengths.tolist())]
    assert got == [reference(ln) for ln in lines]
    doc = desensitize.status()["ds-lone-c"]
    assert doc["host_rows_total"]["no_fusion"] == 2
    assert doc["masked_rows_total"] == 1


def test_no_later_member_of_the_run_binds_the_field_the_mask_rewrites():
    procs = [{"Type": "processor_parse_regex_tpu", "Regex": PARSE,
              "Keys": KEYS}, masker(),
             {"Type": "processor_filter_native",
              "Include": {"url": ".*Signature=.*"}}]
    p = CollectionPipeline()
    assert p.init("ds-after", {"inputs": [], "processors": procs,
                               "flushers": [{"Type": "flusher_stdout"}]})
    assert [[m.spec.kind for m in r.members] for r in p._fused_runs] \
        == [["extract", "mask"]]
    g = run(p, lines_of([b"/a?Signature=s", b"/b?q=1"]))
    # the filter read the masked value, on the host, after the mask
    assert field(g, "url") == [b"/a?Signature=" + STARS]


def test_behind_a_fused_filter_the_kept_rows_are_masked():
    """extract → keep → mask as one program: the mask's pairs were
    computed for every packed row, and the apply reads them through the
    run's row map after the filter compacted the group."""
    procs = [{"Type": "processor_parse_regex_tpu", "Regex": PARSE,
              "Keys": KEYS},
             {"Type": "processor_filter_native", "Include": {"method": "GET"}},
             masker()]
    p = CollectionPipeline()
    assert p.init("ds-kept", {"inputs": [], "processors": procs,
                              "flushers": [{"Type": "flusher_stdout"}]})
    assert [[m.spec.kind for m in r.members] for r in p._fused_runs] \
        == [["extract", "keep", "mask"]]
    urls = random_urls(29, 200)
    lines = [ln.replace(b" GET ", b" POST ") if i % 3 == 0 else ln
             for i, ln in enumerate(lines_of(urls))]
    g = run(p, lines)
    kept = [u for i, u in enumerate(urls) if i % 3 and u is not None]
    assert field(g, "url") == [reference(u) for u in kept]
    doc = desensitize.status()["ds-kept"]
    assert doc["rows_total"] == len(kept)
    assert doc["mask_program_rows_total"] == len(kept)


# -- a demoted chunk ----------------------------------------------------------------

def test_an_injected_fused_dispatch_fault_demotes_to_the_host_same_answers():
    urls = random_urls(17, 400)
    p = build("ds-chaos")
    program = p._fused_runs[0].program()
    chaos.install(ChaosPlan(5, {
        "device_plane.fused_dispatch": FaultSpec(
            prob=1.0, kinds=(chaos.ACTION_ERROR,), max_faults=1)}))
    try:
        g = run(p, lines_of(urls))
    finally:
        chaos.uninstall()
    assert program.demotions == 1
    want = [reference(u) for u in urls]
    assert field(g, "url") == want
    present = sum(u is not None for u in urls)
    assert desensitize.status()["ds-chaos"]["host_rows_total"]["demoted"] \
        == present
    # the next group rides the program again
    g = run(p, lines_of(urls))
    assert program.demotions == 1 and field(g, "url") == want
    assert desensitize.status()["ds-chaos"]["mask_program_rows_total"] \
        == present


# -- the native rebuild, the counters ------------------------------------------------

def test_the_native_rebuild_is_its_python_twins():
    proc = ProcessorDesensitize()
    assert proc.init(masker(), PluginContext())
    values = [b"ab=cd&ef", b"0123456789", b"", b"xyz"]
    arena_src = b"".join(values)
    lens = np.array([len(v) for v in values], np.int32)
    offs = (np.cumsum(lens) - lens).astype(np.int32)
    count = np.array([2, 1, 0, 1], np.int32)
    first = np.array([[3, 6, 0, 0], [10, 0, 0, 0], [0] * 4, [18, 0, 0, 0]],
                     np.int32)
    last = np.array([[5, 8, 0, 0], [18, 0, 0, 0], [0] * 4, [19, 0, 0, 0]],
                    np.int32)
    outs = []
    for how in ("native", "python"):
        arena = np.frombuffer(bytearray(arena_src + b"\0" * 64), np.uint8)
        no, nl = offs.copy(), lens.copy()
        base = len(arena_src)
        if how == "native":
            got = native.mask_spans(arena, offs, lens, count, first, last,
                                    STARS, base, no, nl)
        else:
            rows = np.flatnonzero(count)
            got = proc._rebuild_rows(arena, base, offs, lens, rows,
                                     count[rows], first, last, no, nl)
        outs.append([arena[o:o + n].tobytes() for o, n in zip(no, nl)])
        assert got == 20 + 10 + 10
    assert outs[0] == outs[1] == [b"ab=" + STARS + b"&" + STARS,
                                  b"01" + STARS, b"", STARS + b"yz"]
    # spans that do not lie inside their value: nothing to rely on
    arena = np.frombuffer(bytearray(arena_src + b"\0" * 64), np.uint8)
    bad = first.copy()
    bad[0, 1] = 2                       # behind the first match's end
    assert native.mask_spans(arena, offs, lens, count, bad, last, STARS,
                             len(arena_src), offs.copy(), lens.copy()) is None


def test_counters_add_up_and_the_section_is_on_the_status_page(monkeypatch):
    urls = random_urls(7, 300)
    p = build("ds-count")
    run(p, lines_of(urls))
    monkeypatch.setenv("LOONG_FUSED", "0")
    run(p, lines_of(urls))
    doc = exposition.collect_status()["desensitize"]["ds-count"]
    present = sum(u is not None for u in urls)
    want = [reference(u) for u in urls]
    assert doc["rows_total"] == 600
    assert doc["absent_rows_total"] == 2 * (300 - present)
    assert doc["rows_total"] == doc["mask_program_rows_total"] \
        + sum(doc["host_rows_total"].values()) + doc["absent_rows_total"]
    assert doc["host_rows_total"]["no_fusion"] == present
    assert doc["masked_rows_total"] == 2 * sum(
        w is not None and w != u for u, w in zip(urls, want))
    matches = sum(len(_pairs(BEFORE, CONTENT, u)) for u in urls if u)
    assert doc["matches_total"] == 2 * matches
    assert "desensitize" in exposition.STATUS_SECTIONS


def test_the_repos_regex_alias_is_kept():
    proc = ProcessorDesensitize()
    assert proc.init({"Regex": r"(card=)(\d+)", "ReplacingString": "X"},
                     PluginContext())
    assert proc._plan is None and proc._sub(b"card=12 card=3") \
        == (b"card=X card=X", 2)
    assert not ProcessorDesensitize().init({}, PluginContext())
    assert not ProcessorDesensitize().init(
        masker(Method="sha1"), PluginContext())
