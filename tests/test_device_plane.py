"""Async overlapped device data plane (SURVEY §7 step 4).

Validates, without a chip, the three contracts the plane exists for:

1. dispatch-ahead: with a 20 ms injected device RTT, the engine's pipelined
   chunk path beats the serial dispatch→materialise path by ≥2×;
2. cross-group overlap: the runner keeps one group's device work in flight
   while host-processing its neighbours, beating serial wall-clock;
3. back-pressure: a stalled device fills the in-flight byte budget, the
   runner stops popping, and the bounded process queue rejects pushes at its
   high watermark (BoundedProcessQueue.cpp:89-93 contract extended onto the
   device) — then drains cleanly when the device recovers.
"""

import threading
import time

import numpy as np
import pytest

from loongcollector_tpu.models import PipelineEventGroup, SourceBuffer
from loongcollector_tpu.ops import device_plane as dp
from loongcollector_tpu.ops.device_plane import (DevicePlane,
                                                 LatencyInjectedKernel,
                                                 StallableKernel)
from loongcollector_tpu.ops.regex import engine as engine_mod
from loongcollector_tpu.ops.regex.engine import RegexEngine, get_engine

from conftest import wait_for


@pytest.fixture(autouse=True)
def device_tier(monkeypatch):
    """Force the device tier (not the native host walker) and small chunks
    so a modest event count spans many device dispatches."""
    monkeypatch.setenv("LOONG_NATIVE_T1", "0")
    monkeypatch.setattr(engine_mod, "MAX_BATCH", 256)
    yield
    DevicePlane.reset_for_testing()


def _arena(line: bytes, n: int):
    arena = np.frombuffer(line * n, dtype=np.uint8).copy()
    offsets = np.arange(n, dtype=np.int64) * len(line)
    lengths = np.full(n, len(line), dtype=np.int32)
    return arena, offsets, lengths


class TestPlaneBudget:
    def test_acquire_release_accounting(self):
        plane = DevicePlane.reset_for_testing(budget_bytes=1000)
        k = LatencyInjectedKernel(lambda x: x + 1, 0.0)
        f1 = plane.submit(k, (np.arange(10),), 600)
        assert plane.inflight_bytes() == 600
        got = []
        t = threading.Thread(
            target=lambda: got.append(plane.submit(k, (np.arange(5),), 600)))
        t.start()
        time.sleep(0.15)
        assert not got, "second submit must block over budget"
        np.testing.assert_array_equal(f1.result()[0], np.arange(10) + 1)
        t.join(2)
        assert got, "release must unblock the waiter"
        got[0].result()
        assert plane.inflight_bytes() == 0

    def test_oversize_single_dispatch_admitted(self):
        plane = DevicePlane.reset_for_testing(budget_bytes=100)
        k = LatencyInjectedKernel(lambda x: x * 2, 0.0)
        f = plane.submit(k, (np.arange(4),), 5000)  # > whole budget
        np.testing.assert_array_equal(f.result()[0], np.arange(4) * 2)
        assert plane.inflight_bytes() == 0

    def test_dispatch_error_surfaces_at_result(self):
        plane = DevicePlane.reset_for_testing(budget_bytes=1000)

        def bad(x):
            raise ValueError("boom")

        f = plane.submit(bad, (np.arange(3),), 100)
        assert plane.inflight_bytes() == 100  # held until consumed
        with pytest.raises(ValueError):
            f.result()
        assert plane.inflight_bytes() == 0
        with pytest.raises(ValueError):
            f.result()  # error is sticky, budget released exactly once


class TestEngineDispatchAhead:
    RTT = 0.02

    def test_pipelined_chunks_beat_serial_2x(self):
        DevicePlane.reset_for_testing()
        eng = RegexEngine(r"(\w+) (\d+)")
        assert eng._segment_kernel is not None, "pattern must be tier-1"
        lat = LatencyInjectedKernel(eng._segment_kernel, self.RTT,
                                    serialize=False)
        eng.set_device_kernel_override(lat)
        arena, offsets, lengths = _arena(b"abc 123", 2048)  # 8 chunks of 256

        # warm-up: jit-compile the geometry outside the timed window
        eng.parse_batch(arena[:7 * 8], offsets[:8], lengths[:8])
        n_chunks = 2048 // 256
        t0 = time.perf_counter()
        res = eng.parse_batch(arena, offsets, lengths)
        elapsed = time.perf_counter() - t0

        assert res.ok.all()
        np.testing.assert_array_equal(res.cap_off[:, 0], offsets)
        np.testing.assert_array_equal(res.cap_len[:, 1], 3)
        serial_floor = n_chunks * self.RTT
        assert elapsed < serial_floor / 2, (
            f"pipelined={elapsed*1e3:.1f}ms vs serial floor "
            f"{serial_floor*1e3:.1f}ms — dispatch-ahead not overlapping")

    def test_budget_pressure_still_correct(self):
        # budget of ~1.2 chunks forces drain-while-dispatch interleaving
        DevicePlane.reset_for_testing(budget_bytes=40 * 1024)
        eng = RegexEngine(r"(\w+) (\d+)x")
        assert eng._segment_kernel is not None
        lat = LatencyInjectedKernel(eng._segment_kernel, 0.002,
                                    serialize=False)
        eng.set_device_kernel_override(lat)
        arena, offsets, lengths = _arena(b"abc 123x", 1024)
        res = eng.parse_batch(arena, offsets, lengths)
        assert res.ok.all()
        assert DevicePlane.instance().inflight_bytes() == 0


def _make_group(n_events: int, line: bytes = b"abc 123") -> PipelineEventGroup:
    sb = SourceBuffer()
    g = PipelineEventGroup(sb)
    for _ in range(n_events):
        ev = g.add_log_event(1)
        ev.set_content(sb.copy_string(b"content"), sb.copy_string(line))
    return g


@pytest.fixture()
def stack(tmp_path):
    from loongcollector_tpu.pipeline.pipeline_manager import (
        CollectionPipelineManager, ConfigDiff)
    from loongcollector_tpu.pipeline.queue.process_queue_manager import \
        ProcessQueueManager
    from loongcollector_tpu.pipeline.queue.sender_queue import \
        SenderQueueManager
    from loongcollector_tpu.runner.processor_runner import ProcessorRunner

    pqm = ProcessQueueManager()
    sqm = SenderQueueManager()
    mgr = CollectionPipelineManager(pqm, sqm)
    runner = ProcessorRunner(pqm, mgr, thread_count=1)
    yield pqm, sqm, mgr, runner, ConfigDiff, tmp_path
    mgr.stop_all()
    runner.stop()


def _start_pipeline(mgr, ConfigDiff, tmp_path, pattern, name):
    out_path = tmp_path / f"{name}.jsonl"
    diff = ConfigDiff()
    diff.added[name] = {
        "inputs": [],
        "processors": [{"Type": "processor_parse_regex_tpu",
                        "Regex": pattern, "Keys": ["w", "d"]}],
        "flushers": [{"Type": "flusher_file", "FilePath": str(out_path),
                      "MinCnt": 1, "MinSizeBytes": 1}],
    }
    mgr.update_pipelines(diff)
    pipeline = mgr.find_pipeline(name)
    return pipeline, out_path


class TestRunnerOverlap:
    RTT = 0.04

    def test_cross_group_overlap(self, stack):
        pqm, sqm, mgr, runner, ConfigDiff, tmp_path = stack
        DevicePlane.reset_for_testing()
        pattern = r"(\w+) (\d+)"   # engine-cache key shared with processor
        pipeline, out_path = _start_pipeline(mgr, ConfigDiff, tmp_path,
                                             pattern, "overlap-test")
        eng = get_engine(pattern)
        lat = LatencyInjectedKernel(eng._segment_kernel, self.RTT,
                                    serialize=False)
        eng.set_device_kernel_override(lat)
        try:
            runner.init()
            key = pipeline.process_queue_key
            # warm-up group compiles the kernel geometry
            assert runner.push_queue(key, _make_group(4))
            assert wait_for(lambda: out_path.exists()
                            and out_path.read_text().count("\n") >= 4)

            G = 12
            t0 = time.perf_counter()
            for _ in range(G):
                assert runner.push_queue(key, _make_group(4))
            assert wait_for(
                lambda: out_path.read_text().count("\n") >= 4 * (G + 1),
                timeout=G * self.RTT * 2 + 5)
            elapsed = time.perf_counter() - t0
            serial_floor = G * self.RTT
            assert elapsed < serial_floor * 0.75, (
                f"overlapped={elapsed*1e3:.0f}ms vs serial floor "
                f"{serial_floor*1e3:.0f}ms — runner not overlapping groups")
        finally:
            eng.set_device_kernel_override(None)

    def test_watermark_holds_under_stalled_device(self, stack):
        pqm, sqm, mgr, runner, ConfigDiff, tmp_path = stack
        # budget ≈ one 256×128 chunk: the second group's dispatch must wait
        plane = DevicePlane.reset_for_testing(budget_bytes=40 * 1024)
        pattern = r"(\w+) (\d+)y"
        pipeline, out_path = _start_pipeline(mgr, ConfigDiff, tmp_path,
                                             pattern, "stall-test")
        eng = get_engine(pattern)
        stall = StallableKernel(eng._segment_kernel, rtt_s=0.0)
        eng.set_device_kernel_override(stall)
        stall.stall()
        try:
            runner.init()
            key = pipeline.process_queue_key
            q = pqm.get_queue(key)
            pushed = 0
            for _ in range(q._cap_high + 10):
                if not pqm.push_queue(key, _make_group(4, b"abc 123y")):
                    break
                pushed += 1
            # queue must have hit its high watermark while the device stalls
            assert wait_for(lambda: not pqm.is_valid_to_push(key), timeout=10)
            # the plane bounds device-side work: at most budget + one chunk
            assert plane.inflight_bytes() <= plane.budget_bytes + 40 * 1024
            # loongcolumn: one backlog-aware run (<= run_max_groups) may sit
            # in the blocked worker's hands beyond the queue bound — the
            # buffering window is still hard-bounded, one run wider
            assert pushed <= q._cap_high + 3 + runner.run_max_groups

            stall.unstall()
            assert wait_for(
                lambda: out_path.exists()
                and out_path.read_text().count("\n") >= 4 * pushed,
                timeout=30)
            assert wait_for(lambda: pqm.is_valid_to_push(key), timeout=10)
            assert plane.inflight_bytes() == 0
        finally:
            eng.set_device_kernel_override(None)


class TestDelimiterAsyncSplit:
    """processor_parse_delimiter_tpu rides the same dispatch/complete split
    as the regex processor: device work stays pending across the group
    boundary and applies at complete()."""

    def test_dispatch_defers_then_completes(self, monkeypatch):
        from loongcollector_tpu.pipeline.plugin.interface import \
            PluginContext
        from loongcollector_tpu.processor.parse_delimiter import \
            ProcessorParseDelimiter
        from loongcollector_tpu.processor.split_log_string import \
            ProcessorSplitLogString
        DevicePlane.reset_for_testing()
        ctx = PluginContext()
        p = ProcessorParseDelimiter()
        assert p.init({"Separator": ",", "Keys": ["a", "b", "c"]}, ctx)
        eng = p.engine
        lat = LatencyInjectedKernel(eng._segment_kernel, 0.02,
                                    serialize=False)
        eng.set_device_kernel_override(lat)
        try:
            sb = SourceBuffer()
            g = PipelineEventGroup(sb)
            g.add_raw_event(1).set_content(sb.copy_string(
                b"x,y,z\n1,2,3\n"))
            sp = ProcessorSplitLogString()
            sp.init({}, ctx)
            sp.process(g)
            token = p.process_dispatch(g)
            assert token is not None          # device work in flight
            p.process_complete(g, token)
            cols = g.columns
            assert cols.parse_ok.all()
            arena = g.source_buffer.as_array()
            offs, lens = cols.fields["b"]
            got = [bytes(arena[int(offs[i]):int(offs[i]) + int(lens[i])]
                         .tobytes()) for i in range(2)]
            assert got == [b"y", b"2"]
        finally:
            eng.set_device_kernel_override(None)


class TestBudgetLeakRegression:
    """Round-5 advisor finding: PendingParse.dispatch abandoned submitted
    DeviceFutures when a mid-loop pack/submit raised, permanently leaking
    DevicePlane._inflight budget.  Pre-fix code fails both tests."""

    def test_mid_loop_dispatch_failure_releases_budget(self, monkeypatch):
        DevicePlane.reset_for_testing()
        plane = DevicePlane.instance()
        eng = RegexEngine(r"(\w+) (\d+)")
        assert eng._segment_kernel is not None
        # RTT keeps chunk 1 unmaterialised when chunk 2 fails to pack
        eng.set_device_kernel_override(
            LatencyInjectedKernel(eng._segment_kernel, 0.05,
                                  serialize=False))
        arena, offsets, lengths = _arena(b"abc 123", 1024)  # 4 chunks @256

        # loongstream: packing now goes through the batch ring
        # (ops/device_stream.BatchSlot.pack) — fail at that seam
        from loongcollector_tpu.ops import device_stream as stream_mod
        real_pack = stream_mod.pack_rows
        calls = {"n": 0}

        def failing_pack(*args, **kwargs):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("injected mid-loop pack failure")
            return real_pack(*args, **kwargs)

        monkeypatch.setattr(stream_mod, "pack_rows", failing_pack)
        try:
            with pytest.raises(RuntimeError, match="injected"):
                eng.parse_batch_async(arena, offsets, lengths)
            assert calls["n"] == 2, "failure must hit with a chunk in flight"
            assert plane.inflight_bytes() == 0, (
                "mid-loop dispatch failure stranded in-flight budget")
        finally:
            eng.set_device_kernel_override(None)

    def test_abandoned_future_backstop_releases_budget(self):
        import gc
        plane = DevicePlane.reset_for_testing(budget_bytes=1000)
        k = LatencyInjectedKernel(lambda x: x + 1, 0.0)
        fut = plane.submit(k, (np.arange(8),), 600)
        assert plane.inflight_bytes() == 600
        del fut
        gc.collect()
        assert plane.inflight_bytes() == 0, (
            "dropped DeviceFuture must release budget via finaliser")

    def test_force_release_is_idempotent_with_result(self):
        plane = DevicePlane.reset_for_testing(budget_bytes=1000)
        k = LatencyInjectedKernel(lambda x: x + 1, 0.0)
        fut = plane.submit(k, (np.arange(8),), 600)
        fut.release()
        assert plane.inflight_bytes() == 0
        fut.release()  # double release must not go negative
        assert plane.inflight_bytes() == 0
        with pytest.raises(RuntimeError):
            fut.result()  # released futures surface an error, not data


# ---------------------------------------------------------------------------
# the copy back starts when the dispatch is issued


class _Plain:
    """An output that records, on one shared list, when it was converted
    (what, which output, when).  It cannot start a copy: numpy from a host
    kernel."""

    def __init__(self, value, log, name):
        self._value, self._log, self._name = value, log, name

    def _note(self, what):
        self._log.append((what, self._name, time.perf_counter()))

    def __array__(self, dtype=None, copy=None):
        self._note("asarray")
        return self._value


class _Recording(_Plain):
    """The same with what a `jax.Array` lets the plane see: a copy back
    that can be started ahead.  `fail` makes the start raise."""

    fail = False

    def copy_to_host_async(self):
        self._note("start")
        if self.fail:
            raise RuntimeError("transfer refused")

    def block_until_ready(self):
        return self


class _Refusing(_Recording):
    fail = True


_OUTPUTS = {"plain": _Plain, "dev": _Recording, "bad": _Refusing}


def _recording_kernel(log, kinds):
    """A kernel returning one output per entry of `kinds`: "dev" (starts
    its copy), "plain" (cannot), "bad" (its start raises)."""
    def kernel(x):
        log.append(("kernel", None, time.perf_counter()))
        return tuple(_OUTPUTS[kind](x + i, log, i)
                     for i, kind in enumerate(kinds))
    return kernel


def _events(log):
    return [e[:2] for e in log]


# kinds of the outputs, copies started at submit, counted as prefetched
PREFETCH_CASES = [
    pytest.param(("dev",), [0], 1, id="one_output"),
    pytest.param(("dev", "dev", "dev"), [0, 1, 2], 1, id="three_outputs"),
    pytest.param(("plain", "plain"), [], 0, id="plain_outputs"),
    pytest.param(("dev", "plain", "dev"), [0, 2], 0, id="mixed_outputs"),
    pytest.param(("dev", "bad", "dev"), [0, 1], 0, id="start_raises"),
]


class TestCopyBackStartsAtSubmit:
    @pytest.mark.parametrize("kinds,started,counted", PREFETCH_CASES)
    def test_copies_start_in_submit_once_each_before_result(
            self, kinds, started, counted):
        plane = DevicePlane.reset_for_testing(budget_bytes=1000)
        log = []
        fut = plane.submit(_recording_kernel(log, kinds),
                           (np.arange(4),), 100)      # never raises
        assert _events(log) == [("kernel", None)] + [("start", i)
                                                    for i in started]
        assert plane.utilization()["d2h_prefetched_total"] == counted
        assert plane.utilization()["dispatched_total"] == 1
        assert plane.inflight_bytes() == 100
        out = fut.result()
        # the one result() path: every output converted once, in order,
        # whether its copy was started or not
        assert _events(log)[1 + len(started):] == [
            ("asarray", i) for i in range(len(kinds))]
        for i, o in enumerate(out):
            np.testing.assert_array_equal(o, np.arange(4) + i)
        assert plane.inflight_bytes() == 0
        fut.release()                                  # no second release
        assert plane.inflight_bytes() == 0

    @pytest.mark.parametrize("kinds,started,counted", PREFETCH_CASES)
    def test_release_without_result_returns_the_budget(
            self, kinds, started, counted):
        plane = DevicePlane.reset_for_testing(budget_bytes=1000)
        log = []
        held = plane.submit(LatencyInjectedKernel(lambda x: x, 0.0),
                            (np.arange(2),), 300)
        fut = plane.submit(_recording_kernel(log, kinds),
                           (np.arange(4),), 100)
        assert plane.inflight_bytes() == 400
        fut.release()
        assert plane.inflight_bytes() == 300           # exactly once
        fut.release()
        assert plane.inflight_bytes() == 300
        assert not [e for e in log if e[0] == "asarray"]
        with pytest.raises(RuntimeError):
            fut.result()
        held.result()
        assert plane.inflight_bytes() == 0

    def test_kernel_error_starts_nothing_and_surfaces_at_result(self):
        plane = DevicePlane.reset_for_testing(budget_bytes=1000)

        def bad(x):
            raise ValueError("boom")

        fut = plane.submit(bad, (np.arange(3),), 100)
        assert plane.utilization()["d2h_prefetched_total"] == 0
        with pytest.raises(ValueError):
            fut.result()
        assert plane.inflight_bytes() == 0

    @pytest.mark.parametrize("kinds,started,counted", PREFETCH_CASES)
    def test_legs_recorded_once_and_submit_holds_the_starts(
            self, kinds, started, counted):
        from loongcollector_tpu import trace
        plane = DevicePlane.reset_for_testing(budget_bytes=1000)
        log = []
        t = trace.enable()
        try:
            plane.submit(_recording_kernel(log, kinds),
                         (np.arange(4),), 100).result()
            by = {}
            for s in t.finished_spans():
                by.setdefault(s.name, []).append(s)
        finally:
            trace.disable()
        for leg in ("device.submit", "device.wait", "device.d2h"):
            assert len(by[leg]) == 1, leg
        (submit,) = by["device.submit"]
        stamps = [at for what, _i, at in log if what in ("kernel", "start")]
        assert len(stamps) == 1 + len(started)
        for at in stamps:        # the call and the starts are inside the leg
            assert submit._start_perf <= at \
                <= submit._start_perf + submit.duration_s

    def test_jax_outputs_are_prefetched_and_values_unchanged(self):
        import jax.numpy as jnp
        plane = DevicePlane.reset_for_testing(budget_bytes=1 << 20)
        x = np.arange(12, dtype=np.int32).reshape(3, 4)
        fut = plane.submit(lambda a: (jnp.asarray(a) * 2, jnp.asarray(a).sum(1)),
                           (x,), x.nbytes)
        assert plane.utilization()["d2h_prefetched_total"] == 1
        doubled, sums = fut.result()
        assert isinstance(doubled, np.ndarray)
        np.testing.assert_array_equal(doubled, x * 2)
        np.testing.assert_array_equal(sums, x.sum(1))

    def test_engine_dispatches_all_count_as_prefetched(self):
        plane = DevicePlane.reset_for_testing()
        eng = RegexEngine(r"(\w+) (\d+)")
        arena, offsets, lengths = _arena(b"abc 123", 1024)   # 4 chunks @256
        res = eng.parse_batch(arena, offsets, lengths)
        assert res.ok.all()
        u = plane.utilization()
        assert u["dispatched_total"] >= 4
        assert u["d2h_prefetched_total"] == u["dispatched_total"]
