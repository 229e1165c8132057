"""loongresident (ISSUE 14): single-dispatch pipeline fusion.

Contracts under test:

1. **Single dispatch** — an all-device-capable 3-stage pipeline (filter →
   parse_regex → filter-on-capture) executes in exactly ONE device
   dispatch per batch slot (``FusedProgramKernel.dispatch_count`` and the
   DevicePlane dispatch ledger both asserted), byte-identical to the
   per-stage path.
2. **Planning** — runs form only over statically-bindable consecutive
   stages; unbindable conditions, consumed sources and terminal stages
   end a run; ``LOONG_FUSED=0`` executes per-stage with identical bytes.
3. **Fault isolation** — an injected ``device_plane.fused_dispatch``
   ERROR demotes exactly that chunk to the per-stage dispatch path
   (counted in ``fused_demotions_total``, alarmed once per program), a
   DELAY just rides the window; a real kernel failure demotes too.
4. **Program cache** — content-addressed in-process LRU + the
   ``fused_cache/`` plan record with geometry recovery (cache hit/miss
   counters asserted).
5. **Round-trip win** — under the LatencyInjectedKernel device model the
   fused program beats the staged path ≥ 2× on a 3-stage pipeline (the
   ISSUE acceptance bound; the bench records the same sweep).
6. **Storm** — 8 seeded fused-dispatch storms with the live conservation
   ledger: residual == 0 at mid/post-storm quiesce, zero loss, per-source
   order, and ``fused_demotions_total`` == injected errors.
"""

import json
import time

import numpy as np
import pytest

from loongcollector_tpu import chaos, models
from loongcollector_tpu.chaos import ChaosPlan, FaultSpec
from loongcollector_tpu.models import (ColumnarLogs, PipelineEventGroup,
                                       SourceBuffer)
from loongcollector_tpu.monitor import ledger
from loongcollector_tpu.monitor.alarms import AlarmManager, AlarmType
from loongcollector_tpu.ops import device_stream
from loongcollector_tpu.ops import fused_pipeline as fp
from loongcollector_tpu.ops.device_plane import (DevicePlane,
                                                 LatencyInjectedKernel)
from loongcollector_tpu.pipeline.fused_chain import plan_fusion
from loongcollector_tpu.pipeline.pipeline import CollectionPipeline
from loongcollector_tpu.pipeline.pipeline_manager import (
    CollectionPipelineManager, ConfigDiff)
from loongcollector_tpu.pipeline.queue.process_queue_manager import \
    ProcessQueueManager
from loongcollector_tpu.pipeline.queue.sender_queue import SenderQueueManager
from loongcollector_tpu.runner.processor_runner import ProcessorRunner

from conftest import wait_for

SEEDS = [3, 7, 11, 19, 23, 31, 43, 59]

RX = r"([a-z]+) (\d+)"


@pytest.fixture(autouse=True)
def _fused_env(monkeypatch):
    """Fusion forced on (CPU backend would auto-disable it), fresh device
    plane / ring / program cache per test."""
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


THREE_STAGE = {
    "inputs": [],
    "processors": [
        {"Type": "processor_filter_native",
         "Include": {"content": r"[a-z]+ \d+"}},
        {"Type": "processor_parse_regex_tpu", "Regex": RX,
         "Keys": ["word", "num"]},
        {"Type": "processor_filter_native", "Include": {"num": r"1\d*"}},
    ],
    "flushers": [{"Type": "flusher_stdout"}],
}

LINES = [b"abc 123", b"nope!", b"zz 15", b"yy 25", b"q 1", b"mixed 9x",
         b"deep 1000"]
#: rows surviving filter1 ∧ parse ∧ filter2(num ~ 1\d*) — the re-derived
#: reference the device path must reproduce byte-for-byte
EXPECT = [(b"abc", b"123"), (b"zz", b"15"), (b"q", b"1"),
          (b"deep", b"1000")]


def build_pipeline(config=THREE_STAGE, name="fused-t"):
    p = CollectionPipeline()
    assert p.init(name, dict(config))
    return p


def snapshot(group):
    """Canonical (content, fields) bytes view of a columnar group."""
    cols = group.columns
    arena = group.source_buffer.as_array()
    n = len(cols)
    content = []
    if not cols.content_consumed:
        for i in range(n):
            o, ln = int(cols.offsets[i]), int(cols.lengths[i])
            content.append(bytes(arena[o:o + ln].tobytes()))
    fields = {}
    for k, (offs, lens) in sorted(cols.fields.items()):
        vals = []
        for i in range(n):
            ln = int(lens[i])
            vals.append(None if ln < 0 else
                        bytes(arena[int(offs[i]):int(offs[i]) + ln]
                              .tobytes()))
        fields[k] = vals
    return {"n": n, "content": content, "fields": fields}


def process_one(pipeline, lines):
    g = make_group(lines)
    fin = pipeline.process_begin([g])
    while fin is not None:
        fin = fin()
    return g


# ---------------------------------------------------------------------------
# 1. single dispatch + byte identity


class TestSingleDispatch:
    def test_three_stage_is_one_dispatch_per_batch_slot(self):
        p = build_pipeline()
        assert [(r.head, r.end) for r in p._fused_runs] == [(0, 3)]
        plane = DevicePlane.reset_for_testing()
        g = process_one(p, LINES)
        # THE acceptance assertion: one device dispatch for the whole
        # 3-stage chain over one batch slot
        assert plane.dispatched_total() == 1
        program = p._fused_runs[0].program()
        assert program.dispatch_count == 1
        got = [(w, n) for w, n in zip(snapshot(g)["fields"]["word"],
                                      snapshot(g)["fields"]["num"])]
        assert got == EXPECT
        # second group: one more slot, one more dispatch
        process_one(p, LINES)
        assert plane.dispatched_total() == 2
        assert program.dispatch_count == 2

    @pytest.mark.parametrize("prefetching", [True, False],
                             ids=["prefetching_outputs", "plain_outputs"])
    def test_order_and_values_whether_or_not_outputs_prefetch(
            self, prefetching):
        """The program's own outputs start their copy back at submit; the
        same outputs as numpy (nothing to start) settle through the same
        `result()` to the same rows in the same order."""
        p = build_pipeline(name="fused-pf")
        program = p._fused_runs[0].program()
        plane = DevicePlane.reset_for_testing()
        words = [bytes(97 + i // d % 26 for d in (676, 26, 1))
                 for i in range(600)]
        # every third line falls to the second filter (num ~ 1\d*)
        lines = [b"%s %d%d" % (w, 1 if i % 3 else 2, i)
                 for i, w in enumerate(words)]
        if not prefetching:
            program.set_kernel_override(
                lambda r, l: tuple(np.asarray(o) for o in program._fn(r, l)))
        try:
            groups = [process_one(p, lines[i:i + 200]) for i in (0, 200, 400)]
        finally:
            program.set_kernel_override(None)
        got = [w for g in groups for w in snapshot(g)["fields"]["word"]]
        assert got == [w for i, w in enumerate(words) if i % 3]
        u = plane.utilization()
        assert u["dispatched_total"] == program.dispatch_count >= 3
        assert u["d2h_prefetched_total"] \
            == (u["dispatched_total"] if prefetching else 0)

    def test_byte_identical_to_per_stage_path(self, monkeypatch):
        p_fused = build_pipeline(name="fused-a")
        g1 = process_one(p_fused, LINES)
        assert p_fused._fused_runs[0].program().dispatch_count == 1
        monkeypatch.setenv("LOONG_FUSED", "0")
        p_staged = build_pipeline(name="fused-b")
        g2 = process_one(p_staged, LINES)
        assert snapshot(g1) == snapshot(g2)

    def test_keep_flags_and_rawlog_identical(self, monkeypatch):
        cfg = dict(THREE_STAGE)
        cfg["processors"] = [
            {"Type": "processor_parse_regex_tpu", "Regex": RX,
             "Keys": ["word", "num"], "KeepingSourceWhenParseFail": True},
            {"Type": "processor_filter_native",
             "Include": {"word": r"[a-z]{2,}"}},
        ]
        p_fused = build_pipeline(cfg, name="fused-keep-a")
        assert len(p_fused._fused_runs) == 1
        g1 = process_one(p_fused, LINES)
        monkeypatch.setenv("LOONG_FUSED", "0")
        p_staged = build_pipeline(cfg, name="fused-keep-b")
        g2 = process_one(p_staged, LINES)
        assert snapshot(g1) == snapshot(g2)

    def test_delimiter_extract_stage_fuses(self, monkeypatch):
        cfg = {
            "inputs": [],
            "processors": [
                {"Type": "processor_filter_native",
                 "Include": {"content": r"[a-z]+,.*"}},
                {"Type": "processor_parse_delimiter_tpu", "Separator": ",",
                 "Keys": ["a", "b", "c"]},
            ],
            "flushers": [{"Type": "flusher_stdout"}],
        }
        lines = [b"ab,cd,ef", b"zz,1,2", b"NOPE,x,y", b"q,w"]
        p = build_pipeline(cfg, name="fused-delim-a")
        assert len(p._fused_runs) == 1
        plane = DevicePlane.reset_for_testing()
        g1 = process_one(p, lines)
        assert plane.dispatched_total() == 1
        monkeypatch.setenv("LOONG_FUSED", "0")
        p2 = build_pipeline(cfg, name="fused-delim-b")
        g2 = process_one(p2, lines)
        assert snapshot(g1) == snapshot(g2)

    def test_grok_classify_stage_fuses(self, monkeypatch):
        cfg = {
            "inputs": [],
            "processors": [
                {"Type": "processor_filter_native",
                 "Include": {"content": r"\w+ .*"}},
                {"Type": "processor_grok",
                 "Match": [r"%{WORD:w} %{INT:n}",
                           r"%{WORD:w} %{WORD:v}"]},
            ],
            "flushers": [{"Type": "flusher_stdout"}],
        }
        lines = [b"abc 123", b"abc def", b"!!", b"zz 9"]
        p = build_pipeline(cfg, name="fused-grok-a")
        if not p._fused_runs:
            pytest.skip("grok set did not device-fuse on this host")
        g1 = process_one(p, lines)
        monkeypatch.setenv("LOONG_FUSED", "0")
        p2 = build_pipeline(cfg, name="fused-grok-b")
        g2 = process_one(p2, lines)
        assert snapshot(g1) == snapshot(g2)

    def test_row_path_group_demotes_to_per_stage(self):
        p = build_pipeline(name="fused-rows")
        sb = SourceBuffer(256)
        g = PipelineEventGroup(sb)
        ev = g.add_log_event(1700000002)
        ev.set_content(b"content", sb.copy_string(b"abc 123"))
        fin = p.process_begin([g])
        while fin is not None:
            fin = fin()
        # per-stage path applied the same semantics on the row group
        evs = g.events
        assert len(evs) == 1
        assert evs[0].get_content(b"word").to_bytes() == b"abc"
        assert evs[0].get_content(b"num").to_bytes() == b"123"


# ---------------------------------------------------------------------------
# 2. planning rules


class TestPlanning:
    def test_unbindable_filter_breaks_the_run(self):
        cfg = dict(THREE_STAGE)
        cfg["processors"] = [
            {"Type": "processor_parse_regex_tpu", "Regex": RX,
             "Keys": ["word", "num"]},
            {"Type": "processor_filter_native",
             "Include": {"not_a_capture": r"\d+"}},
        ]
        p = build_pipeline(cfg, name="plan-a")
        assert p._fused_runs == []

    def test_consumed_source_breaks_the_run(self):
        cfg = dict(THREE_STAGE)
        cfg["processors"] = [
            {"Type": "processor_parse_regex_tpu", "Regex": RX,
             "Keys": ["word", "num"]},
            # content was consumed by the parse: a content condition can
            # no longer bind statically
            {"Type": "processor_filter_native",
             "Include": {"content": r".*"}},
        ]
        p = build_pipeline(cfg, name="plan-b")
        assert p._fused_runs == []

    def test_multiline_spec_is_terminal(self):
        from loongcollector_tpu.pipeline.fused_chain import FusionPlanContext
        from loongcollector_tpu.processor.split_multiline import \
            ProcessorSplitMultilineLogString
        from loongcollector_tpu.pipeline.plugin.interface import PluginContext
        proc = ProcessorSplitMultilineLogString()
        assert proc.init({"Multiline": {
            "StartPattern": r"\[\d+\] .*",
            "ContinuePattern": r"\s+.*"}}, PluginContext())
        ms = proc.fused_stage_spec(FusionPlanContext())
        if ms is None:
            pytest.skip("multiline set did not device-fuse on this host")
        assert ms.spec.terminal

    def test_disabled_fusion_runs_per_stage(self, monkeypatch):
        monkeypatch.setenv("LOONG_FUSED", "0")
        p = build_pipeline(name="plan-c")
        assert p._fused_runs  # planned, not executed
        g = process_one(p, LINES)
        got = [(w, n) for w, n in zip(snapshot(g)["fields"]["word"],
                                      snapshot(g)["fields"]["num"])]
        assert got == EXPECT
        assert p._fused_runs[0].program.__self__._program is None \
            if hasattr(p._fused_runs[0].program, "__self__") else True
        assert fp.stage_fusion_status()["programs"] == []

    def test_tuner_floors_keyed_per_program(self):
        p = build_pipeline(name="plan-d")
        process_one(p, LINES)
        chosen = device_stream.auto_tuner().chosen()
        lanes = chosen.get("lane_buckets", {})
        assert any(k.startswith("fused:") for k in lanes), chosen


# ---------------------------------------------------------------------------
# 3. fault isolation / demotion


def _demotions() -> int:
    return int(fp._metrics().counter("fused_demotions_total").value)


class TestDemotion:
    def test_chaos_error_demotes_one_chunk(self):
        p = build_pipeline(name="dem-a")
        before = _demotions()
        AlarmManager.instance().flush()
        chaos.install(ChaosPlan(5, {
            "device_plane.fused_dispatch": FaultSpec(
                prob=1.0, kinds=(chaos.ACTION_ERROR,), max_faults=1)}))
        try:
            g = process_one(p, LINES)
        finally:
            chaos.uninstall()
        got = [(w, n) for w, n in zip(snapshot(g)["fields"]["word"],
                                      snapshot(g)["fields"]["num"])]
        assert got == EXPECT          # demotion never costs answers
        assert _demotions() == before + 1
        program = p._fused_runs[0].program()
        assert program.demotions == 1
        alarms = AlarmManager.instance().flush()
        assert any(a["alarm_type"] == AlarmType.FUSED_DEMOTED.value
                   for a in alarms)

    def test_chaos_delay_is_not_a_demotion(self):
        p = build_pipeline(name="dem-b")
        before = _demotions()
        chaos.install(ChaosPlan(5, {
            "device_plane.fused_dispatch": FaultSpec(
                prob=1.0, kinds=(chaos.ACTION_DELAY,),
                delay_range=(0.0, 0.002), max_faults=4)}))
        try:
            g = process_one(p, LINES)
        finally:
            chaos.uninstall()
        assert _demotions() == before
        got = [(w, n) for w, n in zip(snapshot(g)["fields"]["word"],
                                      snapshot(g)["fields"]["num"])]
        assert got == EXPECT

    def test_kernel_failure_demotes_chunk(self):
        p = build_pipeline(name="dem-c")
        program = p._fused_runs[0].program()
        before = _demotions()

        calls = {"n": 0}

        def broken(rows, lengths):
            calls["n"] += 1
            raise RuntimeError("mosaic says no")

        program.set_kernel_override(broken)
        try:
            g = process_one(p, LINES)
        finally:
            program.set_kernel_override(None)
        assert calls["n"] == 1
        assert _demotions() == before + 1
        got = [(w, n) for w, n in zip(snapshot(g)["fields"]["word"],
                                      snapshot(g)["fields"]["num"])]
        assert got == EXPECT


# ---------------------------------------------------------------------------
# 4. program cache


class TestProgramCache:
    def _hits(self):
        return int(fp._metrics().counter(
            "fused_program_cache_hit_total").value)

    def test_mem_cache_shares_programs_across_pipelines(self):
        p1 = build_pipeline(name="cache-a")
        program1 = p1._fused_runs[0].program()
        before = self._hits()
        p2 = build_pipeline(name="cache-b")
        program2 = p2._fused_runs[0].program()
        assert program1 is program2
        assert self._hits() == before + 1

    def test_disk_plan_roundtrip(self, tmp_path):
        fp.set_cache_dir(str(tmp_path))
        p1 = build_pipeline(name="cache-c")
        program1 = p1._fused_runs[0].program()
        process_one(p1, LINES)     # records the (B, L) geometry
        sig = program1.signature
        path = tmp_path / "fused_cache" / f"v{fp.CACHE_VERSION}_{sig}.json"
        assert path.exists()
        doc = json.loads(path.read_text())
        assert doc["geometries"], doc
        # fresh process model: mem cache cleared, plan reloaded from disk
        fp.reset_for_testing()
        fp.set_cache_dir(str(tmp_path))
        before = self._hits()
        p2 = build_pipeline(name="cache-d")
        program2 = p2._fused_runs[0].program()
        assert program2.signature == sig
        assert self._hits() == before + 1
        assert program2.geometries == program1.geometries

    def test_different_stage_lists_differ(self):
        p1 = build_pipeline(name="cache-e")
        cfg = dict(THREE_STAGE)
        cfg["processors"] = list(THREE_STAGE["processors"][:2])
        p2 = build_pipeline(cfg, name="cache-f")
        assert (p1._fused_runs[0].program().signature
                != p2._fused_runs[0].program().signature)


# ---------------------------------------------------------------------------
# 5. the round-trip model (the ISSUE acceptance ≥2× bound)


class TestRoundtripModel:
    def test_fused_beats_staged_by_2x_under_latency_model(self):
        p = build_pipeline(name="model-a")
        run = p._fused_runs[0]
        program = run.program()
        lines = LINES * 16
        process_one(p, lines)                       # warm fused jit
        g = make_group(lines)
        from loongcollector_tpu.processor.common import extract_source
        src = extract_source(g, run.source_key)
        from loongcollector_tpu.ops.device_batch import (pack_rows,
                                                         pick_length_bucket)
        L = pick_length_bucket(int(src.lengths.max()))
        batch = pack_rows(src.arena, src.offsets, src.lengths, L)
        program.staged_run(batch.rows, batch.lengths)   # warm staged jit
        # ... and the tuple entry the override rides (process_one warmed
        # the packed one, which is what the window dispatches)
        program(batch.rows, batch.lengths)

        rtt, wire = 0.004, 0.002
        n_batches = 5

        fused_kern = LatencyInjectedKernel(program._fn, rtt, serialize=True,
                                           wire_s=wire)
        program.set_kernel_override(fused_kern)
        try:
            t0 = time.perf_counter()
            dispatches = [
                fp.FusedDispatch(program, src.arena, src.offsets,
                                 src.lengths).dispatch()
                for _ in range(n_batches)]
            for d in dispatches:
                d.result()
            fused_s = time.perf_counter() - t0
        finally:
            program.set_kernel_override(None)

        # staged model: each member stage pays its own round trip, one
        # serialized execution stream per stage kernel
        orig = [s.staged for s in program.specs]
        lat = []
        for s in program.specs:
            if s.kind == "keep":
                for c in s.payload:
                    lat.append((c, c.staged,
                                LatencyInjectedKernel(c.staged, rtt,
                                                      wire_s=wire)))
            else:
                lat.append((s, s.staged,
                            LatencyInjectedKernel(s.staged, rtt,
                                                  wire_s=wire)))
        try:
            for obj, _o, k in lat:
                obj.staged = k
            t0 = time.perf_counter()
            for _ in range(n_batches):
                program.staged_run(batch.rows, batch.lengths)
            staged_s = time.perf_counter() - t0
        finally:
            for obj, o, _k in lat:
                obj.staged = o
        ratio = staged_s / fused_s
        assert ratio >= 2.0, (
            f"fused {fused_s*1e3:.1f} ms vs staged {staged_s*1e3:.1f} ms "
            f"— only {ratio:.2f}x under the round-trip model")


# ---------------------------------------------------------------------------
# 6. the 8-seed fused-dispatch storm with the live ledger


def _chunk(src_idx: int, seq: int, n: int) -> bytes:
    return b"\n".join(b"src%d %d" % (src_idx, seq + j)
                      for j in range(n)) + b"\n"


def _raw_group(payload: bytes, source: bytes) -> PipelineEventGroup:
    sb = SourceBuffer(len(payload) + 128)
    g = PipelineEventGroup(sb)
    g.add_raw_event(1700000002).set_content(sb.copy_string(payload))
    g.set_tag(b"__source__", source)
    return g


@pytest.mark.parametrize("seed", SEEDS)
def test_fused_dispatch_storm(seed, tmp_path, monkeypatch):
    monkeypatch.setenv("LOONG_FUSED", "1")
    DevicePlane.reset_for_testing(budget_bytes=2 * 1024 * 1024)
    fp.reset_for_testing()
    demote_before = _demotions()
    ledger.enable()
    ledger.reset()
    auditor = ledger.start_auditor(interval_s=0.05)
    chaos.install(ChaosPlan(seed, {
        "device_plane.fused_dispatch": FaultSpec(
            prob=0.3, kinds=(chaos.ACTION_ERROR,), max_faults=200),
        "device_plane.submit": FaultSpec(
            prob=0.2, kinds=(chaos.ACTION_DELAY,),
            delay_range=(0.0, 0.002), max_faults=50),
    }))
    name = f"fused-storm-{seed}"
    out = tmp_path / f"{name}.jsonl"
    pqm = ProcessQueueManager()
    mgr = CollectionPipelineManager(pqm, SenderQueueManager())
    runner = ProcessorRunner(pqm, mgr, thread_count=4)
    runner.init()
    sources = [b"s%d" % i for i in range(4)]
    try:
        diff = ConfigDiff()
        diff.added[name] = {
            "inputs": [{"Type": "input_static_file_onetime",
                        "FilePaths": ["/nonexistent"]}],
            "global": {"ProcessQueueCapacity": 40},
            "processors": [
                {"Type": "processor_filter_native",
                 "Include": {"content": r"src\d+ \d+"}},
                {"Type": "processor_parse_regex_tpu",
                 "Regex": r"(src\d+) (\d+)", "Keys": ["src", "seq"]},
                {"Type": "processor_filter_native",
                 "Include": {"seq": r"\d+"}},
            ],
            "flushers": [{"Type": "flusher_file", "FilePath": str(out),
                          "MinCnt": 1, "MinSizeBytes": 1}],
        }
        mgr.update_pipelines(diff)
        p = mgr.find_pipeline(name)
        assert p._fused_runs, "storm pipeline must carry a fused run"

        def push_wave(groups_per_source, seq_base):
            total = 0
            for s_i, src in enumerate(sources):
                seq = seq_base
                for _ in range(groups_per_source):
                    g = _raw_group(_chunk(s_i, seq, 8), src)
                    seq += 8
                    deadline = time.monotonic() + 30
                    while not pqm.push_queue(p.process_queue_key, g):
                        assert time.monotonic() < deadline, "push starved"
                        time.sleep(0.002)
                    total += 8
            return total

        total = push_wave(4, 0)
        ledger.assert_conserved(timeout=60, label=f"seed {seed} mid-storm")
        total += push_wave(4, 32)
        assert wait_for(pqm.all_empty, timeout=60)
        time.sleep(0.2)
        ledger.assert_conserved(timeout=60, label=f"seed {seed} post-storm")
        assert auditor.residual_alarms_total == 0
        injected = chaos.fault_counts().get(
            "device_plane.fused_dispatch", 0)
        assert _demotions() - demote_before == injected, (
            f"seed {seed}: {injected} injected errors but "
            f"{_demotions() - demote_before} demotions")
        assert injected > 0, f"seed {seed}: storm never fired"
    finally:
        runner.stop()
        mgr.stop_all()
        chaos.uninstall()
        ledger.stop_auditor()
        ledger.disable()
    per_source = {}
    for line in out.read_text().splitlines():
        obj = json.loads(line)
        if "src" in obj and "seq" in obj:
            per_source.setdefault(obj["src"], []).append(int(obj["seq"]))
    got = sum(len(v) for v in per_source.values())
    assert got == total, f"seed {seed}: lost {total - got} events"
    for src, seqs in per_source.items():
        assert seqs == sorted(seqs), f"seed {seed}: {src} reordered"


# ---------------------------------------------------------------------------
# 7. span-bound DFA match differential


class TestSpanMatch:
    def test_span_match_vs_re(self):
        import re
        from loongcollector_tpu.ops.kernels.dfa_scan import \
            build_dfa_span_match_fn
        from loongcollector_tpu.ops.regex.dfa import compile_dfa
        import jax
        pattern = r"1\d*"
        dfa = compile_dfa(pattern)
        fn = jax.jit(build_dfa_span_match_fn(dfa))
        rng = np.random.RandomState(7)
        rows = np.zeros((16, 32), np.uint8)
        lens = np.zeros(16, np.int32)
        starts = np.zeros(16, np.int32)
        spans = np.zeros(16, np.int32)
        corpus = [b"123", b"15x", b"1", b"", b"912", b"1abc", b"19"]
        ref = re.compile(pattern.encode())
        for i in range(16):
            pre = bytes(rng.randint(97, 123, rng.randint(0, 6),
                                    dtype=np.uint8))
            tok = corpus[i % len(corpus)]
            post = b"tail"[: rng.randint(0, 4)]
            row = pre + tok + post
            rows[i, :len(row)] = np.frombuffer(row, np.uint8)
            lens[i] = len(row)
            starts[i] = len(pre)
            spans[i] = len(tok) if i % 5 else -1   # some absent spans
        got = np.asarray(fn(rows, lens, starts, spans))
        for i in range(16):
            if spans[i] < 0:
                want = False
            else:
                tok = bytes(rows[i, starts[i]:starts[i] + spans[i]]
                            .tobytes())
                want = ref.fullmatch(tok) is not None
            assert bool(got[i]) == want, (i, got[i], want)


# ---------------------------------------------------------------------------
# 8. equivalence gate (the scripts/resident_equivalence.py contract,
#    run in-process on every tier-1 invocation)


class TestEquivalenceGate:
    def test_gate_passes(self, monkeypatch):
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "resident_equivalence",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "scripts",
                "resident_equivalence.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.main() == 0
