"""The column path of processor_parse_timestamp_native against its row path.

`ProcessorParseTimestamp` parses a columnar group's time column with a plan
compiled from `SourceFormat` and hands every row the plan cannot prove to
`_parse_one`.  The plan runs as one native call a group
(`lct_timestamp_column`) or, in a process without the library, as a few
numpy calls.  The guard is differential, three ways: the same groups through
the native call, through the numpy column path (the library withheld) and
through a processor forced onto the row loop (`_plan = None`) must give the
same `cols.timestamps`, row for row, and the same number of PARSE_TIME_FAIL
alarms — on valid stamps and on every mutation of them this file can think
of.  Under `LOONG_DISABLE_NATIVE` the "native" cases run the numpy path too:
the same tests, without the counts only the native call makes."""

import calendar
import os
import random
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from loongcollector_tpu import native
from loongcollector_tpu.models import PipelineEventGroup, SourceBuffer
from loongcollector_tpu.monitor.alarms import AlarmManager
from loongcollector_tpu.pipeline.plugin.interface import PluginContext
from loongcollector_tpu.processor import parse_telemetry
from loongcollector_tpu.processor import parse_timestamp as pt
from loongcollector_tpu.processor.parse_timestamp import (
    COLUMN_MIN_ROWS, ProcessorParseTimestamp)
from loongcollector_tpu.processor.split_log_string import \
    ProcessorSplitLogString

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIPELINE = "ts-columns"
LABEL = f"{ProcessorParseTimestamp.name}/{PIPELINE}"
MONTHS = "Jan Feb Mar Apr May Jun Jul Aug Sep Oct Nov Dec".split()

APACHE = "%d/%b/%Y:%H:%M:%S %z"
FORMATS = {
    "apache": APACHE,
    "iso_space": "%Y-%m-%d %H:%M:%S",
    "compact": "%Y%m%d%H%M%S",
    "literals": "[%Y/%m/%d at %H.%M] 100%% up",
}


def render(fmt, y, mo, d, h, mi, s, z="-0700"):
    """What a writer of `fmt` would print, without asking the calendar
    whether the date exists."""
    out = fmt.replace("%%", "\0")
    for directive, text in (("%Y", f"{y:04d}"), ("%m", f"{mo:02d}"),
                            ("%d", f"{d:02d}"), ("%H", f"{h:02d}"),
                            ("%M", f"{mi:02d}"), ("%S", f"{s:02d}"),
                            ("%b", MONTHS[(mo - 1) % 12]), ("%z", z)):
        out = out.replace(directive, text)
    return out.replace("\0", "%").encode()


def group_of(values):
    """A columnar group with one row per value and a `time` field over
    them (None: the row has no such field), the stamps scattered through
    the arena between the lines as a regex's captures are."""
    sb = SourceBuffer()
    g = PipelineEventGroup(sb)
    g.add_raw_event(100).set_content(sb.copy_string(b"x\n" * len(values)))
    sp = ProcessorSplitLogString()
    sp.init({}, PluginContext(PIPELINE))
    sp.process(g)
    offs = np.zeros(len(values), dtype=np.int64)
    lens = np.full(len(values), -1, dtype=np.int64)
    for i, v in enumerate(values):
        if v is not None:
            view = sb.copy_string(b"[" + v + b"] ")
            offs[i], lens[i] = view.offset + 1, len(v)
    g.columns.set_field("time", offs, lens)
    return g


@pytest.fixture(params=["native", "numpy"])
def path(request, monkeypatch):
    """The column path under test: the native call, or the numpy calls of a
    process without the library."""
    return withhold_library(monkeypatch.setattr, request.param)


def withhold_library(setattr_, path):
    if path == "numpy":
        setattr_(native, "timestamp_column", lambda *a, **k: None)
    return path if native.get_lib() is not None else "numpy"


def column_status(path, rows, fallback, calls, degraded=False):
    """The processor's line of /debug/status `parse` after `calls` groups
    on the column path."""
    want = {"rows": rows, "fallback_rows": fallback, "drift_rows": 0,
            "degraded": degraded}
    if path == "native":
        want.update(native_rows=rows - fallback, native_calls=calls)
    return want


def processor(fmt, tz=None, columns=True):
    p = ProcessorParseTimestamp()
    cfg = {"SourceKey": "time", "SourceFormat": fmt}
    if tz:
        cfg["SourceTimezone"] = tz
    assert p.init(cfg, PluginContext(PIPELINE))
    if not columns:
        p._plan = None          # the row loop, as for a format with no plan
    return p


def alarm_counts():
    """Alarms since the last call, by type."""
    out = {}
    for a in AlarmManager.instance().flush():
        out[a["alarm_type"]] = out.get(a["alarm_type"], 0) \
            + int(a["alarm_count"])
    return out


def run(p, values):
    g = group_of(values)
    alarm_counts()
    p.process(g)
    return g.columns.timestamps.copy(), alarm_counts()


@pytest.fixture(autouse=True)
def _clean_slate():
    parse_telemetry.reset_for_testing()
    alarm_counts()
    yield
    parse_telemetry.reset_for_testing()
    alarm_counts()


def valid_stamps(fmt, rng, n):
    return [render(fmt, rng.randrange(1971, 2100), rng.randrange(1, 13),
                   rng.randrange(1, 29), rng.randrange(24), rng.randrange(60),
                   rng.randrange(60), rng.choice(["-0700", "+0000", "+0530"]))
            for _ in range(n)]


def mutations(fmt, rng):
    """Every way this file knows to make a stamp that is not quite one."""
    base = render(fmt, 2000, 10, 10, 13, rng.randrange(60), rng.randrange(60))
    out = []
    for at in range(len(base)):
        for byte in (b"7", b"x", b" ", b"+", b"-", b":", b"\xc3", b"\x00",
                     b",", b"6", b"T"):
            out.append(base[:at] + byte + base[at + 1:])
    out += [base[:-1], base[1:], base + b"0", b" " + base, b"", b"x"]
    for y, mo, d, h, mi, s in (
            (2023, 2, 31, 0, 0, 0), (2024, 2, 29, 1, 2, 3),
            (2023, 2, 29, 1, 2, 3), (2000, 2, 29, 1, 2, 3),
            (2100, 2, 29, 1, 2, 3), (2024, 4, 31, 0, 0, 0),
            (2024, 12, 31, 23, 59, 59), (2024, 1, 1, 24, 0, 0),
            (2024, 1, 1, 23, 60, 0), (2024, 1, 1, 23, 59, 60),
            (2024, 1, 1, 23, 59, 61), (2024, 1, 0, 0, 0, 0),
            (2024, 0, 1, 0, 0, 0), (2024, 13, 1, 0, 0, 0),
            (1969, 12, 31, 23, 59, 59), (1970, 1, 1, 0, 0, 0),
            (1970, 1, 1, 12, 0, 0), (1900, 1, 1, 0, 0, 0),
            (2369, 12, 31, 23, 59, 59), (2370, 1, 1, 0, 0, 0),
            (9999, 12, 31, 23, 59, 59), (0, 1, 1, 0, 0, 0)):
        out.append(render(fmt, y, mo, d, h, mi, s))
    for z in ("-0799", "Z", "+2400", "-9959", "+07:00", "0700", " 0700",
              "+070a"):
        out.append(render(fmt, 2000, 10, 10, 13, 1, 2, z))
    good = render(fmt, 2000, 10, 10, 13, 1, 2)
    out += [good.replace(b"Oct", b"oct"), good.replace(b"Oct", b"OCT"),
            good.replace(b"Oct", b"Okt"), good.replace(b"10", b" 1", 1),
            good.replace(b"13", b" 1", 1), good.replace(b" ", b"\t"),
            good.replace(b" ", b"  ")]
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("tz", [None, "GMT+08:00", "GMT-03:30"],
                         ids=["local", "plus8", "minus330"])
@pytest.mark.parametrize("name", sorted(FORMATS))
def test_column_path_equals_row_path(name, tz, seed, path):
    fmt = FORMATS[name]
    rng = random.Random(seed * 1000 + len(name))
    values = valid_stamps(fmt, rng, 300) + mutations(fmt, rng) \
        + [None] * 40
    rng.shuffle(values)
    col, row = processor(fmt, tz), processor(fmt, tz, columns=False)
    assert col._plan is not None
    # groups of 256 rows, the last with what is left over
    groups = [values[i:i + 256] for i in range(0, len(values), 256)]
    groups[-2:] = [groups[-2] + groups[-1]]
    for part in groups:
        want, want_alarms = run(row, part)
        got, got_alarms = run(col, part)
        np.testing.assert_array_equal(got, want)
        assert got_alarms.get("PARSE_TIME_FAIL_ALARM", 0) \
            == want_alarms.get("PARSE_TIME_FAIL_ALARM", 0)
        assert (want != 100).any()      # some row was parsed at all
    st = parse_telemetry.status()[LABEL]
    # the plan engaged: it proved rows, and it did not prove the mutants
    assert 0 < st["fallback_rows"] < st["rows"]
    if path == "native":
        assert st["native_rows"] + st["fallback_rows"] == st["rows"]
        assert st["native_calls"] >= len(groups)


@pytest.mark.parametrize("tz", [None, "GMT+08:00"], ids=["local", "plus8"])
@pytest.mark.parametrize("name", sorted(FORMATS))
def test_valid_stamps_never_leave_the_column_path(name, tz, path):
    fmt = FORMATS[name]
    values = valid_stamps(fmt, random.Random(7), 1024)
    want, _ = run(processor(fmt, tz, columns=False), values)
    got, alarms = run(processor(fmt, tz), values)
    np.testing.assert_array_equal(got, want)
    assert (got != 100).all() and not alarms
    # a cold memo: one call names the group's minutes, the next stores
    assert parse_telemetry.status()[LABEL] == column_status(path, 1024, 0, 2)


@pytest.mark.parametrize("fmt", [
    "%y-%m-%d %H:%M:%S", "%Y-%m-%d %H:%M:%S.%f", "%a %b %d %H:%M:%S %Y",
    "%Y-%m-%d %H:%M:%S %Z", "%Y-%j", "%Y-%m-%d %I:%M:%S %p", "%c", "%B %d %Y",
    "%m-%d %H:%M:%S", "%Y-%m-%d %Y", "%Y %b %m", "%Y-%m-%d %z%H", "%Y %",
    "%Y年%m月"])
def test_format_the_plan_cannot_prove_compiles_to_no_plan(fmt):
    p = processor(fmt)
    assert p._plan is None
    assert pt._ColumnPlan.compile(fmt) is None


@pytest.mark.parametrize("fmt,stamp", [
    ("%y-%m-%d %H:%M:%S", b"24-01-02 03:04:05"),
    ("%Y-%m-%d %H:%M:%S.%f", b"2024-01-02 03:04:05.250"),
    ("%a %b %d %H:%M:%S %Y", b"Tue Jan 02 03:04:05 2024"),
    ("%Y-%m-%d %H:%M:%S %Z", b"2024-01-02 03:04:05 UTC"),
])
def test_processor_without_a_plan_is_unchanged(fmt, stamp, monkeypatch):
    p = processor(fmt, "GMT+00:00")
    monkeypatch.setattr(pt._ColumnPlan, "parse", None)     # never called
    monkeypatch.setattr(pt._ColumnPlan, "native_column", None)
    values = [stamp] * 200 + [b"junk"] * 100 + [None] * 10
    got, alarms = run(p, values)
    want = calendar.timegm(time.strptime(stamp.decode(), fmt))
    assert (got[:200] == want).all() and (got[200:] == 100).all()
    assert alarms == {"PARSE_TIME_FAIL_ALARM": 100}
    assert parse_telemetry.status() == {}


@pytest.mark.parametrize("n", [1, 6, COLUMN_MIN_ROWS - 1])
def test_group_under_the_crossover_keeps_the_row_loop(n, monkeypatch, path):
    p = processor(APACHE)
    assert p._plan is not None
    monkeypatch.setattr(pt._ColumnPlan, "parse", None)     # never called
    monkeypatch.setattr(pt.ProcessorParseTimestamp, "_minute_seconds", None)
    # absent rows do not count towards the crossover
    values = valid_stamps(APACHE, random.Random(n), n) + [None] * 500
    got, alarms = run(p, values)
    assert (got[:n] != 100).all() and (got[n:] == 100).all() and not alarms
    # not a fallback: a healthy pipeline that parses after a filter must
    # never reach PARSE_FALLBACK_DEGRADED
    assert parse_telemetry.status() == {}


def test_group_on_the_crossover_reports_rows_that_add_up(monkeypatch, path):
    rng = random.Random(11)
    bad = [b"10/Oct/2000:13:55:36 -07x0", b"31/Feb/2000:13:55:36 -0700",
           b"short"]
    values = valid_stamps(APACHE, rng, COLUMN_MIN_ROWS - len(bad)) + bad \
        + [None] * 7
    rng.shuffle(values)
    calls = []
    parse = pt._ColumnPlan.parse
    p = processor(APACHE)
    monkeypatch.setattr(pt._ColumnPlan, "parse",
                        lambda *a: calls.append(1) or parse(*a))
    _, alarms = run(p, values)
    assert calls == ([] if path == "native" else [1])
    assert parse_telemetry.status()[LABEL] == column_status(
        path, COLUMN_MIN_ROWS, len(bad), 2)
    assert alarms == {"PARSE_TIME_FAIL_ALARM": len(bad)}


def test_stream_of_malformed_stamps_degrades_once_and_alarms_per_row(path):
    p = processor(APACHE)
    rng = random.Random(5)
    total = 0
    for _ in range(3):
        # width W, every one of them: a month that is none
        values = [render(APACHE, 2000, 10, 10, 13, rng.randrange(60),
                         rng.randrange(60)).replace(b"Oct", b"Okt")
                  for _ in range(1024)]
        g = group_of(values)
        p.process(g)
        assert (g.columns.timestamps == 100).all()
        total += len(values)
    alarms = alarm_counts()
    assert alarms == {"PARSE_TIME_FAIL_ALARM": total,
                      "PARSE_FALLBACK_DEGRADED_ALARM": 1}
    # no minute is asked for: the month that is none is a minute's key
    # like another, and `_minute_seconds` has no answer for it, once
    assert parse_telemetry.status()[LABEL] == column_status(
        path, total, total, 4, degraded=True)


def test_event_branch_is_the_row_path():
    g = PipelineEventGroup()
    sb = g.source_buffer
    for i in range(COLUMN_MIN_ROWS + 5):
        ev = g.add_log_event(1)
        ev.set_content(sb.copy_string(b"time"),
                       sb.copy_string(b"2024-01-02 03:04:%02d" % (i % 60)))
    p = processor("%Y-%m-%d %H:%M:%S", "GMT+00:00")
    p.process(g)
    base = calendar.timegm((2024, 1, 2, 3, 4, 0))
    assert [ev.timestamp for ev in g.events] \
        == [base + i % 60 for i in range(COLUMN_MIN_ROWS + 5)]
    assert parse_telemetry.status() == {}


@pytest.mark.parametrize("name", sorted(FORMATS))
def test_plan_reads_the_fields_the_writer_wrote(name):
    fmt = FORMATS[name]
    plan = pt._ColumnPlan.compile(fmt)
    rng = random.Random(13)
    wrote = [(rng.randrange(1, 10000), rng.randrange(1, 13),
              rng.randrange(1, 32), rng.randrange(24), rng.randrange(60),
              rng.randrange(60)) for _ in range(600)]
    stamps = [render(fmt, *w) for w in wrote]
    assert {len(s) for s in stamps} == {plan.width}
    arena = np.frombuffer(b"|".join(stamps), dtype=np.uint8)
    key, second, ok = plan.parse(
        arena, np.arange(len(stamps), dtype=np.int64) * (plan.width + 1))
    assert ok.all()
    for w, k, s in zip(wrote, key.tolist(), second.tolist()):
        if "%S" not in fmt:
            w = w[:5] + (0,)
        assert plan.fields(k) + (s,) == w


def test_offsets_that_leave_the_arena_are_not_gathered(path):
    g = group_of(valid_stamps(APACHE, random.Random(3), 200))
    offs, lens = g.columns.fields["time"]
    offs = offs.copy()
    size = len(g.source_buffer.as_array())
    offs[0], offs[1] = size - 3, size + 10
    g.columns.set_field("time", offs, lens)
    alarm_counts()
    processor(APACHE).process(g)       # no IndexError; the row path has them
    assert (g.columns.timestamps[2:] != 100).all()
    assert (g.columns.timestamps[:2] == 100).all()
    # the native call checks every span; one gather of the numpy path's
    # either takes all the spans or none
    assert parse_telemetry.status()[LABEL] == column_status(
        path, 200, 2 if path == "native" else 200, 2)


def minutes_apart(fmt, n, step_minutes, start=(2021, 3, 4, 5, 6)):
    """`n` stamps `step_minutes` apart, no two in one minute."""
    t0 = calendar.timegm(start + (0,))
    return [render(fmt, *time.gmtime(t0 + i * 60 * step_minutes + i % 60)[:6])
            for i in range(n)]


@pytest.mark.parametrize("tz", [None, "GMT+08:00"], ids=["local", "plus8"])
@pytest.mark.parametrize("sizes", [[5000], [1500] * 4, [4096, 300, 4097]],
                         ids=["one_group", "across_groups", "on_the_cap"])
def test_memo_overflow_past_its_cap_changes_no_answer(sizes, tz, path):
    fmt = FORMATS["iso_space"]
    values = minutes_apart(fmt, sum(sizes), 7)
    assert sum(sizes) > pt._MEMO_MAX
    col, row = processor(fmt, tz), processor(fmt, tz, columns=False)
    for size in sizes:
        part, values = values[:size], values[size:]
        want, want_alarms = run(row, part)
        got, got_alarms = run(col, part)
        np.testing.assert_array_equal(got, want)
        assert (got != 100).all() and not got_alarms and not want_alarms
    assert len(col._minute_memo) <= pt._MEMO_MAX + 1
    assert len(col._minutes[0]) <= max(pt._MEMO_MAX, max(sizes))
    # a second pass over the last group: every minute is in the memo the
    # path under test kept, and the answers are the same
    got, _ = run(col, part)
    np.testing.assert_array_equal(got, want)
    assert parse_telemetry.status()[LABEL] == column_status(
        path, sum(sizes) + sizes[-1], 0, 2 * len(sizes) + 1)


def test_warm_memo_makes_one_native_call_a_group_and_a_new_minute_two(
        monkeypatch):
    if native.get_lib() is None:
        pytest.skip("no native library in this process")
    calls = []
    real = native.timestamp_column
    monkeypatch.setattr(native, "timestamp_column",
                        lambda *a: calls.append(a[3]) or real(*a))
    p = processor(APACHE, "GMT+00:00")
    del calls[:]                    # the plan's own check at init
    monkeypatch.setattr(pt._ColumnPlan, "parse", None)     # never called
    rng = random.Random(17)

    def stamps(minute, n=300):
        return [render(APACHE, 2024, 5, 6, 7, minute, rng.randrange(60))
                for _ in range(n)]

    asked = []
    minute_seconds = p._minute_seconds
    monkeypatch.setattr(p, "_minute_seconds",
                        lambda k: asked.append(k) or minute_seconds(k))
    for values, want_calls, want_asked in (
            (stamps(8) + stamps(9), 2, 2),          # cold: two minutes
            (stamps(8) + stamps(9), 1, 0),          # warm
            (stamps(9) + [None] * 50, 1, 0),
            (stamps(9) + stamps(10), 2, 1),         # one new minute
            (stamps(10) + [b"junk"] * 5, 1, 0)):
        del calls[:], asked[:]
        got, _ = run(p, values)
        assert len(calls) == want_calls and len(asked) == want_asked
        # the first call walks the whole group, the second the rows the
        # first left pending: those of the new minute alone
        assert calls[0] is None
        if want_calls == 2 and want_asked == 1:
            assert len(calls[1]) == 300
        base = calendar.timegm((2024, 5, 6, 7, 0, 0))
        for v, ts in zip(values, got.tolist()):
            if v is None or v == b"junk":
                assert ts == 100
            else:
                assert ts == base + int(v[15:17]) * 60 + int(v[18:20])
    st = parse_telemetry.status()[LABEL]
    assert st == {"rows": 600 * 3 + 300 + 305, "fallback_rows": 5,
                  "drift_rows": 0, "degraded": False,
                  "native_rows": 600 * 3 + 300 + 300, "native_calls": 7}


def test_native_call_reads_the_columns_of_a_span_matrix_as_stored(
        path, monkeypatch):
    # a regex's captures arrive as columns of one [rows, keys] matrix:
    # strided int32 views, which the native call takes without a copy
    values = valid_stamps(APACHE, random.Random(23), 400) + [None] * 30
    random.Random(24).shuffle(values)
    flat, strided = group_of(values), group_of(values)
    offs, lens = flat.columns.fields["time"]
    mats = [np.stack([np.zeros_like(col), col, col + 1], axis=1)
            for col in (offs, lens)]
    del strided.columns.fields["time"]
    strided.columns.set_fields_matrix(["a", "time", "b"], *mats)
    assert not strided.columns.fields["time"][0].flags.c_contiguous
    strides = []
    if path == "native":
        real = native.timestamp_column
        monkeypatch.setattr(native, "timestamp_column",
                            lambda *a: strides.append(a[1].strides)
                            or real(*a))
    p = processor(APACHE)
    p.process(flat)
    p.process(strided)
    np.testing.assert_array_equal(strided.columns.timestamps,
                                  flat.columns.timestamps)
    assert (flat.columns.timestamps != 100).sum() == 400
    if path == "native":
        assert (12,) in strides and (4,) in strides


def test_format_the_native_walk_misreads_leaves_the_row_path(monkeypatch):
    if native.get_lib() is None:
        pytest.skip("no native library in this process")
    assert pt._ColumnPlan.compile(APACHE) is not None
    real = native.timestamp_column

    def misread(*a):
        found = real(*a)
        return found._replace(missing=found.missing + 1)

    monkeypatch.setattr(native, "timestamp_column", misread)
    assert pt._ColumnPlan.compile(APACHE) is None
    assert processor(APACHE)._plan is None


def test_native_source_builds_without_a_warning():
    r = subprocess.run(["make", "-C", os.path.join(REPO, "native"), "lint"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]


# -- the local zone across its transitions, in a process of that zone ---------

_ZONE_SCRIPT = textwrap.dedent('''
    import calendar, sys, time
    import numpy as np
    sys.path.insert(0, {repo!r}); sys.path.insert(0, {tests!r})
    import test_parse_timestamp_columns as t

    t.withhold_library(setattr, {path!r})
    FMT = "%Y-%m-%d %H:%M:%S"
    checked = 0
    for y, mo, d, h in {transitions!r}:
        wall = calendar.timegm((y, mo, d, h, 0, 0))    # the wall clock as if UTC
        # every second of the wall clock from two hours before to two hours
        # after, those the zone skips and those it repeats among them ...
        naive = [time.strftime(FMT, time.gmtime(s)).encode()
                 for s in range(wall - 7200, wall + 7200)]
        # ... and every second of real time around it, as a log has them
        mid = int(time.mktime((y, mo, d, h + 2, 0, 0, 0, 0, -1))) - 7200
        if time.localtime(mid - 7200).tm_gmtoff \\
                == time.localtime(mid + 7200).tm_gmtoff:
            sys.exit(77)                                # no such zone here
        real = [time.strftime(FMT, time.localtime(s)).encode()
                for s in range(mid - 7200, mid + 7200)]
        for stamps in (naive, real) if {skipped_too!r} else (real,):
            groups = [stamps[i:i + 960] for i in range(0, len(stamps), 960)]
            # one processor after the other, each through the whole stretch in
            # order: where a local time has two answers glibc's mktime gives
            # the one nearer its previous answer, so the two are comparable
            # only over the same history
            row, col = t.processor(FMT, columns=False), t.processor(FMT)
            want = [t.run(row, part) for part in groups]
            got = [t.run(col, part) for part in groups]
            for (w, wa), (g, ga) in zip(want, got):
                np.testing.assert_array_equal(g, w)
                assert ga == wa == {{}}, (ga, wa)
                assert (g != 100).all()
                checked += len(g)
    st = t.parse_telemetry.status()[t.LABEL]
    assert st["rows"] == checked, st
    print("OK", checked, st["fallback_rows"])
''')


@pytest.mark.parametrize("zone,transitions,skipped_too,fallback", [
    # 02:00 becomes 03:00 in March, 02:00 becomes 01:00 in November
    ("America/New_York", [(2024, 3, 10, 2), (2024, 11, 3, 2)], True, 0),
    # half an hour, not a whole one: 02:00 becomes 01:30 in April, 02:00
    # becomes 02:30 in October
    ("Australia/Lord_Howe", [(2024, 4, 7, 2), (2024, 10, 6, 2)], True, 0),
    # 44 minutes and 30 seconds: 00:00:00 became 00:44:30, so the minute
    # 00:44 has two offsets and the column path abstains in it (its 30 real
    # seconds go to the row path).  Real time only: of the seconds the zone
    # skipped, the row path's own answer for 00:44:00-29 depends on what it
    # parsed just before, and the two processors parse them at other moments
    ("Africa/Monrovia", [(1972, 1, 7, 0)], False, 30),
])
@pytest.mark.parametrize("path", ["native", "numpy"])
def test_local_zone_across_its_transitions(zone, transitions, skipped_too,
                                           fallback, path):
    script = _ZONE_SCRIPT.format(repo=REPO, tests=os.path.join(REPO, "tests"),
                                 transitions=transitions,
                                 skipped_too=skipped_too, path=path)
    r = subprocess.run([sys.executable, "-c", script],
                       env=dict(os.environ, TZ=zone, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    if r.returncode == 77:
        pytest.skip(f"no tz database entry for {zone} on this host")
    assert r.returncode == 0, r.stderr[-3000:]
    rows = len(transitions) * (2 if skipped_too else 1) * 14400
    assert r.stdout.strip() == f"OK {rows} {fallback}"
