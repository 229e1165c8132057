"""Rehearsal of the benchmark's own arithmetic: schedules and lateness, the
tailer's line→time mapping on a recorded sink, the reduction of a recorded
trace, the bytes function and the roofline, the contract of BENCHMARK.json.
Nothing here measures anything: a CPU run says what is counted, never how
fast."""

import os
import sys

# the benchmark's library lives beside the benchmark, not in the program
sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench"))

import json
import re
import struct
import subprocess
import time

import numpy as np
import pytest

from benchlib import (check, generator, observe, roofline, schedule, spec,
                      stats, tailer, tracered)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BM = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _plugins(config: str, seed: int):
    cfg = spec.load_config(BM, config)
    source = spec.load_module("sources", cfg["source"]["kind"]).make(
        cfg["source"], seed)
    reference = spec.load_module("references", cfg["reference"]["kind"]) \
        .make(cfg["reference"])
    return cfg, source, reference


# -- BENCHMARK.json and the data files ---------------------------------------

def test_benchmark_json_keeps_the_contract():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (BM["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))
    for entry in BM["configs"] + BM["workloads"] + BM["end_to_end"] \
            + BM["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "source", "layer"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in BM["end_to_end"])
    for m in BM["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
    four = sum(1 for w in BM["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BM["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(set(pairs)) == len(pairs)        # a pair appears once


def test_every_name_finds_its_file():
    for c in BM["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in BM["paths"]))
        cfg = spec.load_config(BM, c["name"])
        for key in ("pipeline", "app_config", "environment"):
            assert os.path.exists(os.path.join(cfg["dir"], cfg[key]))
        assert cfg["reduced"] == c["reduced"]
        spec.load_module("sources", cfg["source"]["kind"])
        spec.load_module("references", cfg["reference"]["kind"])
    for w in BM["workloads"]:
        traffic = spec.load_traffic(w["traffic"])
        assert traffic["mode"] in ("open", "closed")
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert callable(spec.load_module("metrics", m["name"]).read)


def test_each_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in BM["workloads"]:
        e2e = [m["name"] for m in
               spec.metrics_of_cell(BM, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layers = spec.metrics_of_cell(BM, w["name"], "per_layer")
        assert layers, w["name"]
        for m in layers:            # each moves a metric this cell reports
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_peaks_table_is_keyed_by_device_kind_and_unknown_is_an_error():
    peaks = spec.load_peaks()
    assert "source" in peaks
    assert roofline.peak_of(peaks, "TPU v5 lite")["hbm_GBps"] == 819
    with pytest.raises(KeyError):
        roofline.peak_of(peaks, "TPU v99")


# -- the line source and the plain reference ---------------------------------

def test_lines_are_the_regression_shape_and_a_pure_function_of_seed():
    cfg, source, reference = _plugins("file_regex_apache_512", 2**31 + 5)
    block = source.block(1000, 4096)
    assert block.shape == (4096, 512) and (block[:, -1] == 10).all()
    assert source.block(3000, 10).tobytes() == block[2000:2010].tobytes()
    assert source.line(1234).endswith(b"\n") and len(source.line(1234)) == 512
    seqs = source.seqs_in(block.tobytes())
    assert (seqs == np.arange(1000, 5096)).all()
    _, other, _ = _plugins("file_regex_apache_512", 2**31 + 6)
    assert other.block(1000, 64).tobytes() != block[:64].tobytes()
    kept = [reference.expected(source.templates[k].tobytes()[:-1])
            for k in range(source.pool)]
    rejected = sum(1 for rec, epoch in kept if "rawLog" in rec)
    assert rejected == round(source.pool * cfg["source"]["reject_share"])
    rec, epoch = reference.expected(source.line(77)[:-1])
    assert ("/%012d?" % 77) in rec.get("url", "") or "rawLog" in rec


def test_filter_reference_keeps_the_corpus_error_share_and_drops_rejects():
    cfg, source, reference = _plugins("file_regex_filter_512", 9)
    kept = [reference.expected(source.templates[k].tobytes()[:-1])
            for k in range(source.pool)]
    n_kept = sum(1 for k in kept if k is not None)
    mix = cfg["source"]["status_mix"]
    errors = sum(n for status, n in mix.items() if status[0] in "45")
    assert errors / sum(mix.values()) == pytest.approx(0.0058, abs=1e-4)
    # 4055 templates the pattern takes, 0.58 % of them: 23 (all 404; a status
    # rarer than one template in the pool gets none)
    assert n_kept == 23
    assert {rec["status"] for rec, _ in (k for k in kept if k is not None)} \
        == {"404"}


def test_statuses_come_in_the_shares_of_the_configurations_mix():
    cfg, source, reference = _plugins("file_regex_apache_512", 11)
    sources = spec.load_module("sources", cfg["source"]["kind"])
    assert sources.apportion({"a": 1, "b": 1, "c": 2}, 5) \
        == ["a", "b", "c", "c", "c"] or sources.apportion(
            {"a": 1, "b": 1, "c": 2}, 5).count("c") >= 2
    seats = sources.apportion(cfg["source"]["status_mix"], 4055)
    assert len(seats) == 4055
    total = sum(cfg["source"]["status_mix"].values())
    for status, n in cfg["source"]["status_mix"].items():
        assert abs(seats.count(status) - 4055 * n / total) < 1
    # and the stream follows the pool: of a million lines the filter's
    # reference keeps 0.56 %
    cfg, source, reference = _plugins("file_regex_filter_512", 11)
    keep = check.keep_mask(source, reference)
    assert keep.sum() == 23
    assert keep[source.template_of(0, 1_000_000)].mean() \
        == pytest.approx(23 / 4096, rel=0.06)
    # any lines, in any order, are the stream's lines
    j = np.array([5, 3, 10**9, 3])
    assert source.block_at(j).tobytes() == b"".join(
        source.line(int(k)) for k in j)


def test_the_width_sits_in_the_query_string_not_in_a_padded_capture():
    cfg, source, reference = _plugins("file_regex_apache_512", 12)
    sizes, urls = [], []
    for k in range(source.pool):
        rec, _ = reference.expected(source.templates[k].tobytes()[:-1])
        if "rawLog" not in rec:
            sizes.append(len(rec["size"]))
            urls.append(rec["url"])
    assert max(sizes) <= 6 and min(sizes) >= 3      # a byte count, not padding
    assert all("?" in u and u[-1] not in "&=?" for u in urls)
    assert min(len(u) for u in urls) > 380          # the url carries the width
    assert all(re.fullmatch(r"[\w/]+\?(\w+=\w*&)*\w+=?\w*", u) for u in urls[:200])


# -- schedules and lateness --------------------------------------------------

def test_exponential_schedule_same_gaps_in_another_order():
    traffic = spec.load_traffic("steady10")
    a, na = schedule.open_schedule(traffic, 4.0, 512, 1)
    b, nb = schedule.open_schedule(traffic, 4.0, 512, 2**31 + 9)
    assert a.size == b.size == round(10e6 * 4 / (8 * 512))
    assert (na == 8).all() and a[-1] < 4.0 and (np.diff(a) > 0).all()
    ga, gb = np.diff(a, prepend=0), np.diff(b, prepend=0)
    assert np.allclose(np.sort(ga), np.sort(gb))      # the same set of gaps
    assert not np.allclose(ga, gb)                    # in another order
    # exponential: the coefficient of variation of the gaps is about 1
    assert 0.9 < ga.std() / ga.mean() < 1.1


def test_every_seed_offers_the_same_work_at_the_window_length():
    traffic = spec.load_traffic("steady10")
    a, na = schedule.open_schedule(traffic, float(BM["run_seconds"]), 512, 1)
    b, nb = schedule.open_schedule(traffic, float(BM["run_seconds"]), 512, 2)
    assert na.sum() == nb.sum() and a.size == b.size
    assert "flush_every_s" not in traffic     # the source's traffic, no more
    with pytest.raises(ValueError):
        schedule.open_schedule(dict(traffic, arrivals="poisson"), 1.0, 512, 1)


def test_a_suffixed_metric_name_is_read_by_the_base_names_reader(tmp_path):
    base = spec.load_module("metrics", "device_row_share")
    for name in ("device_row_share.sat", "device_row_share.tail"):
        assert spec.load_module("metrics", name).read.__code__.co_code \
            == base.read.__code__.co_code
    # a file of the full name wins; a name with neither is an error
    os.makedirs(tmp_path / "metrics")
    (tmp_path / "metrics" / "x.py").write_text("def read(obs):\n    return 1\n")
    (tmp_path / "metrics" / "x.own.py").write_text(
        "def read(obs):\n    return 2\n")
    load = lambda n: spec.load_module(  # noqa: E731
        "metrics", n, bench_dir=str(tmp_path)).read({})
    assert (load("x"), load("x.other"), load("x.own")) == (1, 1, 2)
    with pytest.raises(spec.SpecError):
        load("y.sat")
    # no two files of the benchmark's readers are copies of one another
    mdir = os.path.join(spec.BENCH_DIR, "metrics")
    bodies = {}
    for f in sorted(os.listdir(mdir)):
        if f.endswith(".py"):
            with open(os.path.join(mdir, f)) as fh:
                body = fh.read().split('"""')[-1]
            assert body not in bodies, (f, bodies[body])
            bodies[body] = f


def test_burst_schedule_holds_whole_periods_all_due_at_their_start():
    traffic = spec.load_traffic("burst40")
    due, n = schedule.open_schedule(traffic, 31.0, 512, 3)
    writes = round(traffic["burst_MB"] * 1e6 / (traffic["write_lines"] * 512))
    assert due.size == 15 * writes
    assert set(np.unique(due)) == {2.0 * k for k in range(15)}
    with pytest.raises(ValueError):
        schedule.open_schedule(traffic, 1.0, 512, 3)


def test_generator_writes_the_stream_and_accounts_for_lateness(tmp_path):
    _, source, _ = _plugins("file_regex_apache_512", 4)
    path = str(tmp_path / "in.log")
    gen = generator.Generator(source, path)
    try:
        gen.write(2500)                                   # spans blocks
        t0 = time.monotonic() - 0.05                      # 50 ms late already
        gen.run_open(np.array([0.0, 0.0, 0.2]), np.array([8, 8, 8]), t0)
    finally:
        gen.close()
    with open(path, "rb") as f:
        data = f.read()
    assert data == source.block(0, 2524).tobytes()
    w = gen.writes()
    assert w["first"].tolist() == [0, 2500, 2508, 2516]
    late = (w["done"] - w["due"])[1:]
    assert late[0] >= 0.05 and late[1] >= late[0]   # queued behind the first
    assert 0 <= late[2] < late[0] + 0.5             # the third was not queued
    obs = {"writes": w, "t0": t0, "t1": t0 + 1.0,
           "traffic": {"mode": "open"}}
    assert observe.gen_late_p99_ms(obs) >= 50


# -- the tailer ----------------------------------------------------------------

def _run_tailer(tmp_path, config, recorded, feed, fault=None, seed=7):
    """Feed a recorded sink to a real tailer process in pieces; its result."""
    cfg, _, _ = _plugins(config, seed)
    run_dir = str(tmp_path)
    sink = os.path.join(run_dir, "sink.jsonl")
    doc = {"run_dir": run_dir, "sink": sink, "seed": seed,
           "config": {"source": cfg["source"], "reference": cfg["reference"]},
           "sample_share": 1.0, "fault": fault}
    if fault:
        open(os.path.join(run_dir, "tail.arm"), "w").close()
    with open(os.path.join(run_dir, "tail.json"), "w") as f:
        json.dump(doc, f)
    proc = subprocess.Popen([sys.executable, tailer.__file__,
                             os.path.join(run_dir, "tail.json")])
    times = []
    with open(os.path.join(DATA, recorded), "rb") as f:
        data = f.read()
    prog = os.path.join(run_dir, "tail.progress")
    deadline = time.monotonic() + 30
    while not os.path.exists(prog):              # the tailer is up
        assert time.monotonic() < deadline and proc.poll() is None
        time.sleep(0.01)
    with open(sink, "ab", buffering=0) as out:
        for lo, hi in feed(data):
            before = time.monotonic()
            out.write(data[lo:hi])
            times.append((before, time.monotonic()))
            time.sleep(0.25)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if os.path.exists(prog) and os.path.getsize(prog) == 16:
            with open(prog, "rb") as f:
                if struct.unpack("<qq", f.read(16))[1] >= data.count(b"\n") \
                        - (1 if fault == "drop_row" else 0):
                    break
        time.sleep(0.01)
    open(os.path.join(run_dir, "tail.stop"), "w").close()
    assert proc.wait(timeout=30) == 0
    z = np.load(os.path.join(run_dir, "tail.npz"))
    return {k: z[k] for k in z.files}, times, data


def test_tailer_maps_lines_to_times_on_a_recorded_sink(tmp_path):
    def feed(data):                  # three appends; the second ends mid-record
        ends = [m.end() for m in re.finditer(b"\n", data)]
        return [(0, ends[59]), (ends[59], ends[129] + 100),
                (ends[129] + 100, len(data))]
    tail, times, data = _run_tailer(tmp_path, "file_regex_apache_512",
                                    "sink_regex_seed7_first200.jsonl", feed)
    rows, nbytes, bad_seq, bad_nl, carry = tail["counts"].tolist()
    assert (rows, nbytes, bad_seq, bad_nl, carry) == (200, len(data), 0, 0, 0)
    assert tail["rows_end"][-1] == 200 and tail["last_seq"][-1] == 199
    assert (np.diff(tail["rows_end"]) > 0).all()
    # a read may catch an append half written (a loaded test machine); what
    # counts is that every line is stamped after its append began, and soon
    # after it ended (on the chip host the poll is 0.5 ms)
    obs = {"tail": tail}
    assert observe.settled_lines_at(obs, times[0][0] - 1) == 0
    assert observe.settled_lines_at(obs, times[1][0]) == 60
    assert observe.settled_lines_at(obs, times[2][0]) == 130
    settle = observe.settle_times(obs, np.array([0, 59, 60, 129, 130, 199,
                                                 200]))
    for k, (began, ended) in ((1, times[0]), (3, times[1]), (5, times[2])):
        assert began <= settle[k - 1] <= settle[k] < ended + 0.2
    settle = settle[[0, 1, 2, 5, 6]]
    assert np.isinf(settle[4])                     # a line that never came


def test_tailer_follows_a_filtered_sink_row_to_line(tmp_path):
    tail, _, data = _run_tailer(
        tmp_path, "file_regex_filter_512", "sink_filter_seed7_first48.jsonl",
        lambda d: [(0, len(d))])
    assert tail["counts"].tolist()[:3] == [48, len(data), 0]
    # the 48th kept line is far past line 48: all but 0.56 % are dropped
    assert tail["last_seq"][-1] > 2000


@pytest.mark.parametrize("fault,caught_by", [
    ("drop_row", "seq"), ("dup_row", "seq"), ("swap_rows", "seq"),
    ("alter_field", "record"), ("time_off", "time")])
def test_control_a_broken_guarantee_fails_the_comparison(tmp_path, fault,
                                                         caught_by):
    """The control: the recorded (sound) stream with one guarantee broken on
    its way to the comparison has to come out as not correct."""
    cfg, source, reference = _plugins("file_regex_apache_512", 7)
    tail, _, _ = _run_tailer(tmp_path, "file_regex_apache_512",
                             "sink_regex_seed7_first200.jsonl",
                             lambda d: [(0, len(d))], fault=fault)
    bad_seq = int(tail["counts"][2])
    cmp_ = check.compare_samples(str(tmp_path), tail, source, reference,
                                 0.0)
    got = {"seq": bad_seq, "record": cmp_["bad_record"],
           "time": cmp_["bad_time"]}
    assert got[caught_by] > 0, got
    if caught_by != "seq":
        assert bad_seq == 0


def test_sound_recorded_stream_compares_clean(tmp_path):
    cfg, source, reference = _plugins("file_regex_apache_512", 7)
    tail, _, _ = _run_tailer(tmp_path, "file_regex_apache_512",
                             "sink_regex_seed7_first200.jsonl",
                             lambda d: [(0, len(d))])
    cmp_ = check.compare_samples(str(tmp_path), tail, source, reference,
                                 0.0)
    assert cmp_ == {"compared": 200, "bad_record": 0, "bad_time": 0,
                    "first_bad": []}
    checks = {"a": {"value": 0, "limit": 0}, "b": {"value": 1, "limit": 0}}
    assert not check.verdict(checks)
    assert "b: 1 (limit 0)  <-- FAILS" in check.render(checks)


# -- latency accounting ------------------------------------------------------

def test_latency_is_timed_from_due_and_missing_lines_miss_the_tail():
    w = {"due": np.array([9.0, 10.0, 10.5, 12.0]),
         "done": np.array([9.0, 10.3, 10.5, 12.0]),
         "first": np.array([0, 8, 16, 24]), "count": np.array([8, 8, 8, 8])}
    tail = {"t": np.array([10.1, 10.9]), "last_seq": np.array([11, 19]),
            "bytes_end": np.array([6000, 10000])}
    obs = {"writes": w, "tail": tail, "t0": 10.0, "t1": 12.0,
           "line_bytes": 512, "traffic": {"mode": "open"}}
    seqs, due = observe.due_lines(obs)
    assert seqs.tolist() == list(range(8, 24))        # due in [t0, t1) only
    lat = observe.latencies_ms(obs)
    assert np.allclose(lat[:4], 100.0)                # from DUE, not from done
    assert np.allclose(lat[4:8], 900.0)
    assert np.allclose(lat[8:12], 400.0)
    assert np.isinf(lat[12:]).all()                   # never settled
    assert np.isinf(observe.e2f_percentile(obs, 95))
    assert observe.e2f_percentile(obs, 50) == pytest.approx(650.0)
    assert observe.gen_late_p99_ms(obs) == pytest.approx(297.0)
    # whole-window bytes: lines settled by t1 less lines settled by t0
    assert observe.delivered_bytes(obs) == 20 * 512
    assert observe.sink_flush_KiB_p50(obs) == pytest.approx(5000 / 1024)


def test_statistics():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([1, 2, 3, np.inf], 99) == np.inf
    buckets = {"0.001": 0, "0.002": 4, "0.004": 10, "+Inf": 10}
    assert stats.histogram_quantile(buckets, 0.5) == 0.004
    assert stats.histogram_quantile({"0.001": 0}, 0.5) is None


# -- the trace reduction -----------------------------------------------------

@pytest.fixture(scope="module")
def recorded_trace():
    doc = spec.load_json(os.path.join(DATA, "trace_events_small.json"))
    events = [[e[0], e[1], doc["names"][e[2]], e[3], e[4]]
              for e in doc["events"]]
    return events, doc["spans"], doc["mark_perf_ns"]


def test_trace_reduction_on_a_recorded_trace(recorded_trace):
    events, spans, mark_perf_ns = recorded_trace
    ops = tracered.device_ops(events)
    assert ops and all(o[0] == "/device:TPU:0" for o in ops)
    lo = min(o[2] for o in ops)
    hi = max(o[2] + o[3] for o in ops)
    busy = tracered.busy_seconds(ops, lo, hi)
    by_name = tracered.op_seconds(ops)
    assert 0 < busy <= sum(by_name.values()) + 1e-12   # a union, not a sum
    assert busy / ((hi - lo) / 1e9) < 0.05             # the chip mostly waits
    kernel = [k for k in by_name if k.startswith("_extract")]
    assert kernel == ["_extract.1"]
    assert by_name["_extract.1"] == max(by_name.values())
    calls = tracered.kernel_calls(ops, "_extract")
    assert len(calls) == 40
    assert roofline.extract_shapes(calls[0][0]) == (1024, 512, 9)
    # the mark puts the spans and the device on one clock
    mark_ns = tracered.mark_start_ns(events)
    assert mark_ns is not None

    def to_s(ns):
        return (np.asarray(ns) - mark_ns) / 1e9 + mark_perf_ns / 1e9
    gaps = tracered.idle_gaps_by_span(ops, spans, float(to_s(lo)),
                                      float(to_s(hi)), to_s)
    assert sum(gaps.values()) == pytest.approx((hi - lo) / 1e9 - busy,
                                               rel=1e-6)
    assert any(k.startswith(("processor.", "flusher.", "device.", "pipeline."))
               for k in gaps)
    assert len(tracered.top(gaps, 3)) <= 3


def test_idle_gaps_go_to_the_innermost_open_span_instant_by_instant():
    # busy [1,2] and [5,6] of a window [0,10]; A [0.5,4] holds B [2.5,3]
    ops = [("/device:TPU:0", "%x = y", 1e9, 1e9),
           ("/device:TPU:0", "%x = y", 5e9, 1e9)]
    spans = [["A", 0.5, 3.5, 1, None, {}], ["B", 2.5, 0.5, 2, 1, {}],
             ["C", 7.0, 1.0, 3, None, {}]]
    gaps = tracered.idle_gaps_by_span(ops, spans, 0.0, 10.0,
                                      lambda ns: np.asarray(ns) / 1e9)
    assert gaps == pytest.approx({"_no_span_": 4.5, "A": 2.0, "B": 0.5,
                                  "C": 1.0})


def test_union_of_intervals():
    s, e = tracered.union_intervals([0, 5, 6, 20], [10, 2, 10, 1])
    assert s.tolist() == [0, 20] and e.tolist() == [16, 21]
    ops = [("/device:TPU:0", "%a = x", 0.0, 10.0),
           ("/device:TPU:0", "%b = y", 5.0, 10.0),
           ("/device:TPU:1", "%a = x", 0.0, 5.0)]
    # averaged over the chips used: (15 + 5) / 2 ns
    assert tracered.busy_seconds(ops, 0.0, 100.0) == pytest.approx(10e-9)
    assert tracered.short_name("%copy-start = (u8[1,2]) copy-start(x)") \
        == "_copy-start"


def test_self_time_takes_children_out():
    spans = [["pipeline.process", 0.0, 1.0, 1, None, {}],
             ["processor.a", 0.1, 0.3, 2, 1, {}],
             ["processor.a", 0.5, 0.2, 3, 1, {}],
             ["device.roundtrip", 0.15, 0.1, 4, 2, {}]]
    self_s = tracered.self_seconds(spans)
    assert self_s["pipeline.process"] == pytest.approx(0.5)
    assert self_s["processor.a"] == pytest.approx(0.4)
    assert self_s["device.roundtrip"] == pytest.approx(0.1)


# -- the bytes function and the roofline -------------------------------------

def test_bytes_function_and_roofline_on_known_shapes(recorded_trace):
    assert roofline.extract_bytes(1024, 512, 9) \
        == 1024 * 512 + 4 * 1024 + 1024 * 9 * 8 == 602112
    peak = roofline.peak_of(spec.load_peaks(), "TPU v5 lite")
    # 602,112 bytes at 819 GB/s take 0.735 us; a kernel that took 100 us
    # reads 0.735 % of its roofline
    assert roofline.hbm_roofline_pct(602112, 100e-6, peak) \
        == pytest.approx(0.7352, rel=1e-3)
    assert roofline.extract_shapes("%fusion = f32[8] fusion()") is None
    events, _, _ = recorded_trace
    obs = {"trace": {"events": events}, "peaks": spec.load_peaks(),
           "device": {"kind": "TPU v5 lite"}}
    pct = observe.extract_roofline(obs)
    us = observe.extract_us_per_MiB(obs)
    assert 0 < pct < 100          # a share of a roofline never passes it
    assert pct == pytest.approx(100 * 602112 / 819e9 / (us * 0.5e-6), rel=1e-6)
    assert observe.extract_roofline({"trace": None}) is None   # nothing to read
    obs["device"]["kind"] = "TPU v99"
    with pytest.raises(KeyError):
        observe.extract_roofline(obs)


def test_counter_readers_return_nothing_when_there_is_nothing():
    empty = {"status0": {}, "status1": {}, "ledger0": {}, "ledger1": {},
             "metrics0": {}, "metrics1": {}, "polls": [], "spans": None,
             "trace": None, "config": {"pipeline_name": "bench"},
             "t0": 0.0, "t1": 1.0}
    for reader in (observe.device_row_share, observe.pad_row_share,
                   observe.compiles_in_window, observe.fused_dispatch_share,
                   observe.queue_wait_p50_ms, observe.read_lag_bytes,
                   observe.device_busy, observe.device_idle_share):
        assert reader(empty) is None, reader.__name__
    assert observe.span_seconds(empty, "processor.", True) is None
    status = lambda rows, pad, host: {  # noqa: E731
        "streaming": {"ring": {"real_rows": rows, "padded_rows": pad}},
        "device": {"routing": {"rows": {"host_walker": host, "cpu_re": 0}}}}
    obs = dict(empty, status0=status(100, 0, 50), status1=status(1000, 100, 150))
    assert observe.device_row_share(obs) == pytest.approx(0.9)
    assert observe.pad_row_share(obs) == pytest.approx(0.1)
