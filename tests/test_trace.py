"""loongtrace: span layer, deterministic timeline, histograms, exposition.

The ISSUE 3 acceptance spine lives here:

  * a single seeded chaos storm produces a deterministic trace timeline
    containing the injected faults, breaker transitions and spill/replay
    events — re-running the same seed yields BYTE-IDENTICAL span
    structure (`TestDeterministicTimeline`);
  * histograms are retrievable via the Prometheus-text endpoint and
    traces flow as self-telemetry PipelineEventGroups
    (`TestExposition`, `TestSelfMonitorTraces`);
  * the `MetricsRecord.snapshot(reset_counters=True)` read-reset race is
    fixed: concurrent adds are never lost (`TestMetricsRaces`);
  * metric records owned by runners/breakers retire on stop
    (`TestRecordOwnership`).
"""

import threading
import time
import urllib.request

import numpy as np
import pytest

from loongcollector_tpu import chaos, trace
from loongcollector_tpu.chaos import ChaosPlan, FaultSpec
from loongcollector_tpu.monitor.alarms import AlarmManager
from loongcollector_tpu.monitor import exposition
from loongcollector_tpu.monitor.metrics import (Histogram, MetricsRecord,
                                                ReadMetrics, WriteMetrics)
from loongcollector_tpu.monitor.self_monitor import SelfMonitorServer
from loongcollector_tpu.ops.device_plane import (DevicePlane,
                                                 LatencyInjectedKernel,
                                                 roundtrip_histogram)
from loongcollector_tpu.pipeline.queue.process_queue_manager import \
    ProcessQueueManager
from loongcollector_tpu.pipeline.queue.sender_queue import (SenderQueueItem,
                                                            SenderQueueManager)
from loongcollector_tpu.runner.circuit import BreakerState, SinkCircuitBreaker
from loongcollector_tpu.runner.disk_buffer import DiskBufferWriter
from loongcollector_tpu.runner.flusher_runner import FlusherRunner
from loongcollector_tpu.runner.processor_runner import ProcessorRunner
from loongcollector_tpu.trace import TraceConfig


def _own(sp) -> dict:
    """A span's attributes as its call site gave them: without the CPU
    seconds and the thread id every span gets when it is recorded."""
    return {k: v for k, v in sp.attrs.items() if k not in ("cpu_s", "tid")}


@pytest.fixture(autouse=True)
def _clean():
    chaos.reset()
    trace.disable()
    yield
    chaos.reset()      # full reset: later tests must not see our storms
    trace.disable()
    # breaker trips in the storm tests raise SINK_CIRCUIT_OPEN alarms on
    # the process-wide singleton — drain them or they poison other files
    AlarmManager.instance().flush()


# ---------------------------------------------------------------------------
# disabled-path contract


class TestDisabledPath:
    def test_hooks_are_noops(self):
        assert not trace.is_active()
        assert trace.active_tracer() is None
        assert trace.start_span("x") is None
        assert trace.current_span() is None
        trace.event("x", a=1)          # swallowed, no tracer to record it
        with trace.span("y"):
            pass
        tracer = trace.enable()
        assert tracer.finished_spans() == []
        assert tracer.timeline() == []

    def test_scoped_activation(self):
        with trace.active() as t:
            assert trace.is_active()
            trace.event("inside")
            assert len(t.timeline()) == 1
        assert not trace.is_active()

    def test_env_activation(self):
        assert not trace.install_from_env({})
        assert not trace.install_from_env({"LOONG_TRACE": "0"})
        assert trace.install_from_env({"LOONG_TRACE": "1",
                                       "LOONG_TRACE_SAMPLE": "0.25",
                                       "LOONG_TRACE_SEED": "7"})
        t = trace.active_tracer()
        assert t.config.sample_rate == 0.25
        assert t.config.seed == 7


# ---------------------------------------------------------------------------
# spans


class TestSpans:
    def test_span_lifecycle_and_parenting(self):
        t = trace.enable()
        root = t.start_span("root", trace_id="g:0")
        t.push_current(root)
        child = t.start_span("child")
        assert child.parent_id == root.span_id
        assert child.trace_id == "g:0"
        child.end()
        trace.event("boom", k=1)       # attaches to current root span
        t.pop_current(root)
        root.end()
        spans = {s.name: s for s in t.finished_spans()}
        assert set(spans) == {"root", "child"}
        assert spans["root"].duration_s is not None
        assert [e[0] for e in spans["root"].events] == ["boom"]
        # the timeline keeps the event too, linked to the span
        (ev,) = t.timeline()
        assert ev.span_id == root.span_id

    def test_end_is_idempotent(self):
        t = trace.enable()
        sp = t.start_span("once")
        sp.end()
        sp.end("error")
        assert len(t.finished_spans()) == 1
        assert t.finished_spans()[0].status == "ok"

    def test_context_manager_records_error_status(self):
        t = trace.enable()
        with pytest.raises(ValueError):
            with trace.span("risky"):
                raise ValueError("x")
        assert t.finished_spans()[0].status == "error"


# ---------------------------------------------------------------------------
# deterministic sampling


class TestDeterministicSampling:
    def test_same_seed_same_verdicts(self):
        t1 = trace.enable(TraceConfig(sample_rate=0.5, seed=11))
        v1 = [t1.should_sample(f"p:{i}") for i in range(200)]
        t2 = trace.enable(TraceConfig(sample_rate=0.5, seed=11))
        v2 = [t2.should_sample(f"p:{i}") for i in range(200)]
        assert v1 == v2
        assert any(v1) and not all(v1)

    def test_different_seeds_diverge(self):
        a = trace.Tracer(TraceConfig(sample_rate=0.5, seed=1))
        b = trace.Tracer(TraceConfig(sample_rate=0.5, seed=2))
        assert [a.should_sample(f"p:{i}") for i in range(64)] != \
            [b.should_sample(f"p:{i}") for i in range(64)]

    def test_rate_extremes(self):
        t = trace.Tracer(TraceConfig(sample_rate=1.0))
        assert all(t.should_sample(f"k:{i}") for i in range(8))
        t = trace.Tracer(TraceConfig(sample_rate=0.0))
        assert not any(t.should_sample(f"k:{i}") for i in range(8))

    def test_group_keys_are_stable_sequences(self):
        t = trace.enable()
        assert t.next_group_key("p1") == "p1:0"
        assert t.next_group_key("p1") == "p1:1"
        assert t.next_group_key("p2") == "p2:0"


# ---------------------------------------------------------------------------
# the acceptance spine: seeded storm → deterministic, byte-identical trace


class _Q:
    def __init__(self):
        self.items = []

    def push(self, item):
        self.items.append(item)
        return True


class _StormFlusher:
    name = "flusher_storm"
    queue_key = 1

    def __init__(self):
        self.sender_queue = _Q()

    def spill_identity(self):
        return {"pipeline": "storm", "flusher_type": self.name,
                "plugin_id": "flusher_storm/0"}


def _run_seeded_storm(seed, tmp_path, tag):
    """One single-threaded storm through REAL components: chaos
    faultpoints, a SinkCircuitBreaker, DiskBufferWriter spill/replay and
    DevicePlane round-trips — everything the timeline must witness."""
    tracer = trace.enable(TraceConfig(seed=seed))
    br = SinkCircuitBreaker("storm/sink", failure_threshold=2,
                            cooldown_s=0.0)
    db = DiskBufferWriter(str(tmp_path / f"storm-{tag}"))
    flusher = _StormFlusher()
    plane = DevicePlane(budget_bytes=1 << 20)
    kernel = LatencyInjectedKernel(lambda x: x + 1, rtt_s=0.0)
    arr = np.arange(4, dtype=np.int64)
    plan = ChaosPlan(seed, {
        "http_sink.send": FaultSpec(prob=0.45, delay_range=(0.0, 0.0),
                                    max_faults=10),
        "device_plane.submit": FaultSpec(prob=0.3, delay_range=(0.0, 0.0),
                                         max_faults=6),
    })
    with chaos.active(plan):
        for i in range(40):
            try:
                chaos.faultpoint("http_sink.send", exc=ConnectionError)
                br.on_success()
            except ConnectionError:
                br.on_failure()
                if br.state is not BreakerState.CLOSED:
                    item = SenderQueueItem(b"payload-%d" % i, 8,
                                           flusher=flusher, queue_key=1)
                    assert db.spill(item, flusher.spill_identity())
                    br.note_spilled()
            if br.state is not BreakerState.CLOSED and br.allow_probe():
                br.on_success()                       # probe → re-close
        for _ in range(12):
            fut = plane.submit(kernel, (arr,), nbytes=64)
            try:
                fut.result()
            except chaos.ChaosFault:
                pass
        db.replay(lambda identity: flusher)
    structure = tracer.structure_bytes()
    by_name = tracer.timeline_by_name()
    schedule = chaos.schedule()
    br.mark_deleted()
    trace.disable()
    return structure, by_name, schedule


class TestDeterministicTimeline:
    SEED = 20240803

    def test_storm_timeline_is_complete_and_reproducible(self, tmp_path):
        s1, by_name, schedule = _run_seeded_storm(self.SEED, tmp_path, "a")
        # every injected fault is on the timeline — zero silent injections
        injected = {(e.attrs["point"], e.attrs["hit"], e.attrs["action"])
                    for e in by_name["chaos.inject"]}
        assert injected == {(p, h, a) for (p, h, a, _d, _m) in schedule}
        assert injected, "storm injected nothing"
        # breaker transitions and spill/replay are all visible
        assert by_name.get("breaker.open"), "no breaker.open on timeline"
        assert by_name.get("breaker.half_open")
        assert by_name.get("breaker.close")
        assert by_name.get("disk_buffer.spill"), "no spill on timeline"
        assert by_name.get("disk_buffer.replay"), "no replay on timeline"
        # the same seed re-runs to BYTE-IDENTICAL span structure
        s2, _, _ = _run_seeded_storm(self.SEED, tmp_path, "b")
        assert s1 == s2

    def test_different_seeds_produce_different_structure(self, tmp_path):
        s1, _, _ = _run_seeded_storm(3, tmp_path, "c")
        s2, _, _ = _run_seeded_storm(4, tmp_path, "d")
        assert s1 != s2


# ---------------------------------------------------------------------------
# device plane: the submit→resolve stopwatch


class TestDeviceRoundtrip:
    def test_stopwatch_feeds_histogram_and_spans(self):
        base = roundtrip_histogram().count
        plane = DevicePlane(budget_bytes=1 << 20)
        kernel = LatencyInjectedKernel(lambda x: x * 2, rtt_s=0.002)
        t = trace.enable()
        fut = plane.submit(kernel, (np.arange(8, dtype=np.int64),),
                           nbytes=64)
        assert fut.result()[0][1] == 2
        assert roundtrip_histogram().count == base + 1
        assert roundtrip_histogram().snapshot()["max"] >= 0.002
        (sp,) = [s for s in t.finished_spans()
                 if s.name == "device.roundtrip"]
        assert sp.status == "ok"
        assert sp.attrs["nbytes"] == 64
        assert sp.duration_s >= 0.002

    def test_errored_future_ends_span_error(self):
        plane = DevicePlane(budget_bytes=1 << 20)
        t = trace.enable()

        def boom(x):
            raise RuntimeError("kernel exploded")

        fut = plane.submit(boom, (np.arange(2),), nbytes=8)
        with pytest.raises(RuntimeError):
            fut.result()
        (sp,) = [s for s in t.finished_spans()
                 if s.name == "device.roundtrip"]
        assert sp.status == "error"
        assert plane.inflight_bytes() == 0


# ---------------------------------------------------------------------------
# histogram


class TestHistogram:
    def test_log2_buckets_and_percentiles(self):
        h = Histogram("t_seconds")
        for _ in range(90):
            h.observe(0.001)
        for _ in range(10):
            h.observe(1.0)
        s = h.snapshot()
        assert s["count"] == 100
        assert 0.001 <= s["p50"] <= 0.002048
        assert 0.001 <= s["p90"] <= 0.002048
        assert s["p99"] == 1.0          # clamped to observed max
        assert s["max"] == 1.0
        assert abs(s["sum"] - (0.09 + 10.0)) < 1e-9

    def test_overflow_and_negative_clamp(self):
        h = Histogram("t_seconds", base=1e-6, n_buckets=4)
        h.observe(10.0)                 # way past the top finite bucket
        h.observe(-5.0)                 # clamped to zero
        buckets = h.buckets()
        assert buckets[-1][0] == float("inf")
        assert buckets[-1][1] == 2
        assert buckets[0][1] == 1       # the clamped zero
        assert h.snapshot()["max"] == 10.0

    def test_reset_semantics(self):
        h = Histogram("t_seconds")
        h.observe(0.5)
        assert h.snapshot(reset=True)["count"] == 1
        assert h.snapshot()["count"] == 0

    def test_concurrent_observe_conserves_count(self):
        h = Histogram("t_seconds")

        def worker():
            for _ in range(2000):
                h.observe(0.001)

        ts = [threading.Thread(target=worker) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert h.snapshot()["count"] == 8000

    def test_record_registration_and_snapshot_shape(self):
        rec = MetricsRecord(category="test_hist")
        h = rec.histogram("lat_seconds")
        assert rec.histogram("lat_seconds") is h
        h.observe(0.01)
        snap = rec.snapshot()
        assert snap["histograms"]["lat_seconds"]["count"] == 1
        rec.mark_deleted()


# ---------------------------------------------------------------------------
# the snapshot race fix


class TestMetricsRaces:
    def test_reset_snapshot_never_loses_adds(self):
        """Two threads: one hammers add(1), one snapshots with reset.
        Conservation law: sum of drained deltas + residual == total adds.
        Pre-fix, an add could land between a counter's read and reset and
        vanish."""
        rec = MetricsRecord(category="race_test")
        c = rec.counter("hits_total")
        n_adds = 50_000
        drained = []
        stop = threading.Event()

        def snapshotter():
            while not stop.is_set():
                drained.append(rec.snapshot(
                    reset_counters=True)["counters"]["hits_total"])

        t = threading.Thread(target=snapshotter)
        t.start()
        for _ in range(n_adds):
            c.add(1)
        stop.set()
        t.join()
        residual = rec.snapshot(reset_counters=True)["counters"]["hits_total"]
        assert sum(drained) + residual == n_adds
        rec.mark_deleted()

    def test_concurrent_registration_during_snapshot(self):
        """First-touch registration mid-snapshot must never blow up the
        iteration (the chaos plane registers fault counters lazily during
        storms, racing the self-monitor's snapshot loop)."""
        rec = MetricsRecord(category="race_test")
        stop = threading.Event()
        errors = []

        def registrar():
            i = 0
            while not stop.is_set():
                # bounded name space: the race needs first-touch inserts
                # racing the snapshot iteration, not unbounded dict growth
                # (unbounded, each snapshot gets quadratically slower and
                # the test wedges under adverse scheduling)
                rec.counter(f"c{i % 256}_total").add(1)
                rec.gauge(f"g{i % 256}").set(1.0)
                i += 1

        def snapshotter():
            try:
                for _ in range(300):
                    rec.snapshot(reset_counters=True)
            except RuntimeError as e:    # "dict changed size during iteration"
                errors.append(e)
            finally:
                stop.set()

        ts = [threading.Thread(target=registrar),
              threading.Thread(target=snapshotter)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errors
        rec.mark_deleted()

    def test_name_validation_and_kind_uniqueness(self):
        rec = MetricsRecord(category="val_test")
        with pytest.raises(ValueError):
            rec.counter("Not-Snake")
        rec.counter("depth_total")
        with pytest.raises(ValueError):
            rec.gauge("depth_total")     # same name, different kind
        rec.mark_deleted()


# ---------------------------------------------------------------------------
# record ownership: runners/breakers retire their records on stop


class TestRecordOwnership:
    def _live(self):
        WriteMetrics.instance().gc_deleted()
        return len(WriteMetrics.instance().records())

    def test_flusher_runner_and_breakers_retire_on_stop(self):
        base = self._live()
        runner = FlusherRunner(SenderQueueManager(), None)
        flusher = _StormFlusher()
        item = SenderQueueItem(b"x", 1, flusher=flusher, queue_key=9)
        runner.breaker_for(item)         # creates a breaker record
        assert self._live() == base + 2
        runner.stop(drain=False)
        assert self._live() == base

    def test_processor_runner_retires_on_stop(self):
        base = self._live()
        runner = ProcessorRunner(ProcessQueueManager(), None,
                                 thread_count=1)
        assert self._live() == base + 1
        runner.init()
        runner.stop()
        assert self._live() == base


# ---------------------------------------------------------------------------
# exposition endpoint + self-telemetry


class TestExposition:
    def test_render_includes_histograms_and_labels(self):
        rec = MetricsRecord(category="expo_test", labels={"sink": "s1"})
        rec.counter("sent_total").add(4)
        rec.histogram("rtt_seconds").observe(0.004)
        text = exposition.render()
        rec.mark_deleted()
        assert '<' not in text.split("\n")[0]
        assert 'loong_sent_total{category="expo_test",sink="s1"} 4' in text
        assert "# TYPE loong_rtt_seconds histogram" in text
        assert 'loong_rtt_seconds_bucket{category="expo_test",' \
            'sink="s1",le="+Inf"} 1' in text
        assert "loong_rtt_seconds_count" in text
        assert "loong_rtt_seconds_p99" in text

    def test_render_does_not_reset_counters(self):
        rec = MetricsRecord(category="expo_test2")
        rec.counter("kept_total").add(7)
        exposition.render()
        assert rec.counter("kept_total").value == 7
        rec.mark_deleted()

    def test_http_endpoint_serves_storm_histograms(self, tmp_path):
        """Acceptance leg: after a seeded storm the latency histograms are
        retrievable over the Prometheus endpoint."""
        _run_seeded_storm(42, tmp_path, "expo")
        server = exposition.ExpositionServer(0)
        assert server.start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics",
                timeout=5).read().decode()
        finally:
            server.stop()
        assert "loong_device_roundtrip_seconds_bucket" in body
        assert "loong_device_roundtrip_seconds_p50" in body
        # 404 for anything else, and stop() is idempotent
        server.stop()

    def test_start_from_env(self):
        assert exposition.start_from_env({}) is None
        assert exposition.start_from_env({"LOONG_EXPO_PORT": "bogus"}) is None
        server = exposition.start_from_env({"LOONG_EXPO_PORT": "0"})
        assert server is not None
        server.stop()


class TestSelfMonitorTraces:
    def test_traces_flow_as_event_groups(self, tmp_path):
        """Acceptance leg: the storm's spans/events flow to sinks as
        PipelineEventGroups through the self-monitor pipeline."""
        tracer = trace.enable()
        trace.event("chaos.inject", point="x", hit=0, action="error")
        sp = tracer.start_span("pipeline.process", trace_id="p:0")
        sp.end()
        pqm = ProcessQueueManager()
        pqm.create_or_reuse_queue(201)
        pqm.create_or_reuse_queue(202)
        server = SelfMonitorServer()
        server.process_queue_manager = pqm
        server.set_metrics_pipeline(201)
        server.set_traces_pipeline(202)
        server.send_once()
        key, group = pqm.pop_item(timeout=0)
        while key != 202:
            key, group = pqm.pop_item(timeout=0)
        assert bytes(group.get_tag(b"__source__")) == b"loongtrace"
        kinds = set()
        names = set()
        for ev in group.events:
            c = {bytes(k): bytes(v) for k, v in ev.contents}
            kinds.add(c[b"kind"])
            names.add(c[b"name"])
        assert kinds == {b"span", b"event"}
        assert {b"chaos.inject", b"pipeline.process"} <= names
        # drained: a second send has nothing trace-wise
        assert tracer.finished_spans() == []
        assert tracer.timeline() == []

    def test_histogram_percentiles_flatten_into_metrics_group(self):
        rec = MetricsRecord(category="selfmon_hist",
                            labels={"pipeline_name": "px"})
        rec.histogram("wait_seconds").observe(0.01)
        pqm = ProcessQueueManager()
        pqm.create_or_reuse_queue(211)
        server = SelfMonitorServer()
        server.process_queue_manager = pqm
        server.set_metrics_pipeline(211)
        server.send_once()
        rec.mark_deleted()
        found = {}
        while True:
            item = pqm.pop_item(timeout=0)
            if item is None:
                break
            _, group = item
            for ev in group.events:
                if str(ev.name) == "selfmon_hist":
                    found = ev.value.values
        assert found, "histogram record never reached the metrics group"
        keys = {k.decode() for k in found}
        assert {"wait_seconds_count", "wait_seconds_p50", "wait_seconds_p99",
                "wait_seconds_max"} <= keys


# ---------------------------------------------------------------------------
# timeline bounds


class TestTimelineBounds:
    def test_span_events_are_bounded(self):
        t = trace.enable()
        sp = t.start_span("busy")
        for i in range(500):
            sp.add_event("e", i=i)
        sp.end()
        assert len(t.finished_spans()[0].events) <= 256

    def test_drain_returns_everything_once(self):
        t = trace.enable()
        t.start_span("a").end()
        trace.event("x")
        spans, events = t.drain()
        assert len(spans) == 1 and len(events) == 1
        spans, events = t.drain()
        assert spans == [] and events == []


# ---------------------------------------------------------------------------
# PR 25: host spans that tell work from waiting — nested stages, the device
# legs, the reader, the sink and the pauses in the one tracer; always-on
# file-input counters and start-up phases


import gc
import os
import json

from loongcollector_tpu.flusher.file import FlusherFile
from loongcollector_tpu.input.file.file_server import (FileInputStats,
                                                       FileServer,
                                                       _ConfigState)
from loongcollector_tpu.input.file.polling import FileDiscoveryConfig
from loongcollector_tpu.input.file.reader import LogFileReader
from loongcollector_tpu.models import PipelineEventGroup
from loongcollector_tpu.monitor import startup
from loongcollector_tpu.ops import xprof
from loongcollector_tpu.pipeline.plugin.instance import (FlusherInstance,
                                                         ProcessorInstance)
from loongcollector_tpu.pipeline.plugin.interface import (PluginContext,
                                                          Processor)

LEGS = ("device.submit", "device.wait", "device.d2h")


class _DeviceStage(Processor):
    """A processor with the split dispatch/complete protocol whose device
    work is one dispatch through the plane (slow enough to wait on)."""

    name = "processor_stub_device"
    supports_async_dispatch = True
    supports_columnar = True

    def __init__(self, plane):
        super().__init__()
        self.plane = plane
        self.kernel = LatencyInjectedKernel(lambda x: x + 1, rtt_s=0.004)

    def process_dispatch(self, group):
        return self.plane.submit(self.kernel,
                                 (np.arange(8, dtype=np.int64),), nbytes=64)

    def process_complete(self, group, token):
        token.result()


def _one_group() -> PipelineEventGroup:
    g = PipelineEventGroup()
    ev = g.add_log_event(1)
    ev.set_content(g.source_buffer.copy_string(b"k"),
                   g.source_buffer.copy_string(b"v"))
    return g


def _staged_roundtrip(with_xprof: bool):
    """One group through dispatch → complete under a `pipeline.process`
    root, as the runner's overlapped loop drives it.  Returns the spans by
    name and the root."""
    plane = DevicePlane(budget_bytes=1 << 20)
    inst = ProcessorInstance(_DeviceStage(plane), "stub/0")
    t = trace.enable()
    timeline = xprof.enable() if with_xprof else None
    try:
        root = t.start_span("pipeline.process", trace_id="p:0")
        t.push_current(root)
        groups = [_one_group()]
        tokens = inst.process_dispatch(groups)
        assert t.current_span() is root      # the stage popped itself
        t.pop_current(root)                  # detach, as the runner does
        t.push_current(root)                 # re-attach at completion
        inst.process_complete(groups, tokens)
        assert t.current_span() is root
        t.pop_current(root)
        root.end("ok")
        by_name = {}
        for s in t.finished_spans():
            by_name.setdefault(s.name, []).append(s)
        return by_name, root, timeline
    finally:
        trace.disable()
        xprof.disable()


class TestNestedStages:
    def test_complete_children_nest_and_self_time_excludes_them(self):
        by, _root, _tl = _staged_roundtrip(False)
        (complete,) = by["processor.processor_stub_device.complete"]
        kids = [s for n in ("device.wait", "device.d2h") for s in by[n]]
        assert kids and all(s.parent_id == complete.span_id for s in kids)
        waited = sum(s.duration_s for s in kids)
        assert waited >= 0.003               # the stage really waited
        self_time = complete.duration_s - waited
        assert 0.0 <= self_time < complete.duration_s - 0.003

    def test_dispatch_holds_the_submit_leg(self):
        by, _root, _tl = _staged_roundtrip(False)
        (dispatch,) = by["processor.processor_stub_device.dispatch"]
        (submit,) = by["device.submit"]
        assert submit.parent_id == dispatch.span_id
        assert _own(submit) == {"nbytes": 64}

    def test_roundtrip_keeps_the_root_as_parent(self):
        by, root, _tl = _staged_roundtrip(False)
        (rt,) = by["device.roundtrip"]
        (dispatch,) = by["processor.processor_stub_device.dispatch"]
        assert rt.parent_id == root.span_id != dispatch.span_id
        assert rt.trace_id == "p:0"
        # it outlives the stage that submitted it
        assert rt.duration_s > dispatch.duration_s

    @pytest.mark.parametrize("name", [
        "processor.processor_stub_device.dispatch",
        "processor.processor_stub_device.complete"])
    def test_stage_spans_hang_from_the_root(self, name):
        by, root, _tl = _staged_roundtrip(False)
        (stage,) = by[name]
        assert stage.parent_id == root.span_id and stage.status == "ok"

    def test_rootless_stage_is_current_for_its_body(self):
        seen = []

        class _Peek(Processor):
            name = "processor_stub_peek"
            supports_columnar = True

            def process(self, group):
                seen.append(trace.current_span().name)

        t = trace.enable()
        ProcessorInstance(_Peek(), "peek/0").process([_one_group()])
        assert seen == ["processor.processor_stub_peek"]
        assert t.current_span() is None

    def test_a_raising_stage_pops_itself(self):
        class _Boom(Processor):
            name = "processor_stub_boom"
            supports_columnar = True

            def process(self, group):
                raise RuntimeError("boom")

        t = trace.enable()
        with pytest.raises(RuntimeError):
            ProcessorInstance(_Boom(), "boom/0").process([_one_group()])
        assert t.current_span() is None
        (sp,) = t.finished_spans()
        assert sp.status == "error"


class TestDeviceLegs:
    @pytest.mark.parametrize("leg", LEGS)
    def test_leg_with_xprof_off(self, leg):
        by, _root, _tl = _staged_roundtrip(False)
        (sp,) = by[leg]
        assert _own(sp) == {"nbytes": 64}    # no dispatch id without xprof
        assert sp.duration_s >= 0.0

    @pytest.mark.parametrize("leg", LEGS)
    def test_leg_with_xprof_on_carries_the_one_dispatch_id(self, leg):
        by, _root, timeline = _staged_roundtrip(True)
        (rec,) = timeline.dispatches()
        (sp,) = by[leg]
        assert _own(sp) == {"nbytes": 64, "dispatch_id": rec.id}
        assert by["device.roundtrip"][0].attrs["dispatch_id"] == rec.id

    def test_one_measurement_feeds_both_planes(self):
        by, _root, timeline = _staged_roundtrip(True)
        (rec,) = timeline.dispatches()
        legs = {name: (t0 + timeline.epoch, dur)
                for name, t0, dur, _a in rec.legs}
        for span_name, leg in (("device.submit", "submit"),
                               ("device.wait", "exec"),
                               ("device.d2h", "d2h")):
            (sp,) = by[span_name]
            assert sp._start_perf == pytest.approx(legs[leg][0], abs=1e-9)
            assert sp.duration_s == pytest.approx(legs[leg][1], abs=1e-12)

    def test_wait_is_split_from_copy_without_xprof(self):
        by, _root, _tl = _staged_roundtrip(False)
        assert by["device.wait"][0].duration_s >= 0.003   # the rtt
        assert by["device.d2h"][0].duration_s < 0.003

    def test_pack_span_from_the_callers_stopwatch(self):
        plane = DevicePlane(budget_bytes=1 << 20)
        t = trace.enable()
        with t.start_span("processor.x.dispatch") as stage:
            t.push_current(stage)
            t0 = time.perf_counter()
            fut = plane.submit(lambda x: x, (np.arange(4),), nbytes=32)
            xprof.note_dispatch(fut, "regex", "4x8", t0, 0.002)
            fut.result()
        (pack,) = [s for s in t.finished_spans() if s.name == "device.pack"]
        assert pack.parent_id == stage.span_id
        assert _own(pack) == {"nbytes": 32}
        assert pack._start_perf == t0 and pack.duration_s == 0.002

    def test_acquire_span_only_when_the_budget_blocks(self):
        plane = DevicePlane(budget_bytes=100)
        kernel = LatencyInjectedKernel(lambda x: x, rtt_s=0.0)
        t = trace.enable()
        held = [plane.submit(kernel, (np.arange(2),), nbytes=80)]

        def drain_one():
            if not held:
                return False
            held.pop().result()
            return True

        fut = plane.submit(kernel, (np.arange(2),), nbytes=80,
                           on_wait=drain_one)       # must wait for 80 bytes
        fut.result()
        plane.submit(kernel, (np.arange(2),), nbytes=80).result()  # fits
        acquires = [s for s in t.finished_spans()
                    if s.name == "device.acquire"]
        assert len(acquires) == 1
        assert _own(acquires[0]) == {"nbytes": 80, "on": "budget"}
        # what it drained while waiting nests under it
        waits = [s for s in t.finished_spans() if s.name == "device.wait"
                 and s.parent_id == acquires[0].span_id]
        assert len(waits) == 1

    def test_disabled_result_takes_no_legs(self):
        plane = DevicePlane(budget_bytes=1 << 20)
        fut = plane.submit(lambda x: x, (np.arange(4),), nbytes=32)
        assert fut.result()[0][3] == 3 and not trace.is_active()


def _flusher(tmp_path, **config):
    f = FlusherFile()
    ctx = PluginContext(pipeline_name="p")
    assert f.init({"FilePath": str(tmp_path / "sink.jsonl"), **config}, ctx)
    return f, FlusherInstance(f, "flusher_file/0")


class TestSinkSpans:
    @pytest.mark.parametrize("name", ["flusher.serialize", "flusher.write"])
    def test_size_triggered_flush_is_rootless_on_the_sender(self, tmp_path,
                                                            name):
        # the worker's share of a size-triggered flush is the hand-over
        # (`flusher.enqueue` under `flusher.send`); serialize and write
        # run on the sink's sender thread, under no span of the worker's
        f, inst = _flusher(tmp_path, MinSizeBytes=1)
        t = trace.enable()
        try:
            assert inst.send(_one_group())
        finally:
            f.stop()
        (send,) = [s for s in t.finished_spans() if s.name == "flusher.send"]
        (enq,) = [s for s in t.finished_spans()
                  if s.name == "flusher.enqueue"]
        assert enq.parent_id == send.span_id
        assert _own(enq) == {"flusher": "flusher_file", "groups": 1,
                             "events": 1}
        (sp,) = [s for s in t.finished_spans() if s.name == name]
        assert sp.parent_id is None
        assert sp.attrs["groups"] == 1 and sp.attrs["events"] == 1
        assert sp.attrs["nbytes"] == os.path.getsize(tmp_path / "sink.jsonl")

    @pytest.mark.parametrize("name", ["flusher.serialize", "flusher.write"])
    def test_timeout_thread_flush_is_rootless(self, tmp_path, name):
        f, inst = _flusher(tmp_path, MinSizeBytes=1 << 30, TimeoutSecs=0.01)
        t = trace.enable()
        try:
            assert inst.send(_one_group())       # staged, nothing flushed
            assert not [s for s in t.finished_spans() if s.name == name]
            time.sleep(0.02)
            th = threading.Thread(target=f.batcher.flush_timeout)
            th.start()
            th.join()
        finally:
            f.stop()
        (sp,) = [s for s in t.finished_spans() if s.name == name]
        assert sp.parent_id is None and sp.attrs["flusher"] == "flusher_file"
        assert os.path.getsize(tmp_path / "sink.jsonl") == sp.attrs["nbytes"]

    def test_flush_with_tracing_off_writes_the_same_bytes(self, tmp_path):
        f, inst = _flusher(tmp_path, MinSizeBytes=1)
        assert inst.send(_one_group())
        f.stop()
        doc = json.loads((tmp_path / "sink.jsonl").read_text())
        assert doc["k"] == "v"


class TestReaderSpan:
    def test_read_span_carries_what_the_timeline_event_did(self, tmp_path):
        p = tmp_path / "a.log"
        p.write_bytes(b"one\ntwo\nthree\n")
        t = trace.enable()
        r = LogFileReader(str(p), presplit_lines=True)
        g = r.read()
        assert g is not None and r.read() is None     # nothing more: no span
        (sp,) = [s for s in t.finished_spans()
                 if s.name == "input.file.read"]
        # what the `input.read` timeline event said is on the span
        assert _own(sp) == {"path": str(p), "offset": 0, "nbytes": 14,
                            "rows": len(g)}
        assert "input.read" not in t.timeline_by_name()

    def test_read_with_tracing_off(self, tmp_path):
        p = tmp_path / "a.log"
        p.write_bytes(b"one\n")
        assert LogFileReader(str(p)).read() is not None


class TestPauses:
    def test_gc_span_after_a_collection_and_hook_gone_after_disable(self):
        before = list(gc.callbacks)
        t = trace.enable()
        assert len(gc.callbacks) == len(before) + 1
        gc.collect()
        spans = [s for s in t.finished_spans() if s.name == "runtime.gc"]
        assert spans and _own(spans[-1]) == {"generation": 2}
        trace.disable()
        assert gc.callbacks == before
        gc.collect()                               # nothing listens now

    def test_gc_nests_under_the_stage_it_interrupted(self):
        t = trace.enable()
        stage = t.start_stage("processor", "processor.x")
        gc.collect()
        stage.end()
        (g,) = [s for s in t.finished_spans() if s.name == "runtime.gc"]
        assert g.parent_id == stage.span_id

    def test_young_collections_feed_the_histogram_not_the_store(self):
        t = trace.enable()
        hist = t.span_histogram("runtime.gc")
        base = hist.count
        for _ in range(3):
            gc.collect(0)
        t.start_span("x").end()                    # folds what is pending
        assert hist.count >= base + 3
        stored = [s for s in t.finished_spans() if s.name == "runtime.gc"
                  and s.attrs["generation"] == 0]
        assert all(s.duration_s >= 1e-3 for s in stored)

    def test_volatile_spans_stay_out_of_the_structure(self):
        t = trace.enable()
        t.start_span("a").end()
        one = t.structure_bytes()
        gc.collect()
        with trace.span("checkpoint.dump"):
            pass
        assert t.structure_bytes() == one

    @pytest.mark.parametrize("name", ["checkpoint.dump", "ledger.audit",
                                      "self_monitor.tick"])
    def test_periodic_work_gets_a_span(self, name, tmp_path):
        t = trace.enable()
        if name == "checkpoint.dump":
            from loongcollector_tpu.input.file.checkpoint import \
                CheckPointManager
            mgr = CheckPointManager()
            mgr.path = str(tmp_path / "cp.json")
            mgr.last_dump = 0.0
            mgr.dump_periodically(0.0)
        elif name == "ledger.audit":
            from loongcollector_tpu.monitor import ledger
            aud = ledger.ConservationAuditor(ledger.EventLedger(),
                                             interval_s=0.01)
            aud.start()
            time.sleep(0.08)
            aud.stop()
        else:
            mon = SelfMonitorServer()
            mon.interval_s = 0.0
            mon.start()
            time.sleep(0.7)
            mon.stop()
        assert [s for s in t.finished_spans() if s.name == name]

    def test_nothing_installed_while_tracing_is_off(self, tmp_path):
        before = list(gc.callbacks)
        from loongcollector_tpu.input.file.checkpoint import \
            CheckPointManager
        mgr = CheckPointManager()
        mgr.path = str(tmp_path / "cp.json")
        mgr.dump_periodically(0.0)
        assert gc.callbacks == before and os.path.exists(mgr.path)


class TestSpanHistogramsAndStatus:
    def test_every_finished_span_lands_in_loong_span_seconds(self):
        t = trace.enable()
        base = t.span_histogram("stage.a").count
        for _ in range(4):
            t.start_span("stage.a").end()
        assert t.span_histogram("stage.a").count == base + 4
        text = exposition.render()
        assert 'loong_span_seconds_count{category="trace",name="stage.a"}' \
            in text
        assert 'loong_span_seconds_bucket{category="trace",name="stage.a"' \
            in text

    def test_the_ring_keeps_the_newest_and_counts_what_it_evicts(
            self, monkeypatch):
        import collections
        from loongcollector_tpu.trace import tracer as tracer_mod
        monkeypatch.setattr(tracer_mod, "_SPAN_CAP", 4)
        t = trace.enable()
        t._spans = collections.deque(maxlen=4)
        for i in range(6):
            t.start_span(f"s{i}").end()
        assert [s.name for s in t.finished_spans()] == ["s2", "s3", "s4",
                                                        "s5"]
        assert t.stats() == {"spans": 4, "events": 0, "dropped_spans": 2,
                             "cpu_clock": tracer_mod.cpu_clock()}

    @pytest.mark.parametrize("key", ["spans", "events", "dropped_spans"])
    def test_status_trace_section(self, key):
        assert "trace" not in exposition.collect_status()   # off: absent
        t = trace.enable()
        t.start_span("a").end()
        doc = exposition.collect_status()["trace"]
        assert key in doc and doc["dropped_spans"] == 0 and doc["spans"] == 1

    @pytest.mark.parametrize("key", [
        "rounds_total", "rounds_throttled_total",
        "throttle_sleep_seconds_total", "reads_total", "read_bytes_total",
        "reads_blocked_total", "push_rejected_total"])
    def test_status_file_input_section(self, key, monkeypatch):
        fs = FileServer()
        monkeypatch.setattr(FileServer, "_instance", fs)
        doc = exposition.collect_status()["file_input"]
        assert key in doc
        assert doc["rounds_throttled_total"] == {"3": 0, "8": 0}
        assert "file_input" in exposition.STATUS_SECTIONS

    @pytest.mark.parametrize("phase", startup.PHASES)
    def test_status_startup_section(self, phase, monkeypatch):
        monkeypatch.setattr(startup, "_phases", {})
        assert "startup" not in exposition.collect_status()
        for p in startup.PHASES:
            startup.mark(p)
        first = dict(startup._phases)
        startup.mark(phase)                        # written once
        doc = exposition.collect_status()["startup"]
        assert doc == first and doc[phase] >= 0.0
        assert list(doc) == list(startup.PHASES)

    def test_first_dispatch_marks_its_phase_once(self, monkeypatch):
        from loongcollector_tpu.ops import device_plane as dp
        monkeypatch.setattr(startup, "_phases", {})
        monkeypatch.setattr(dp, "_first_dispatch_marked", False)
        plane = DevicePlane(budget_bytes=1 << 20)
        plane.submit(lambda x: x, (np.arange(2),), nbytes=8).result()
        t_first = startup.status()["first_dispatch"]
        plane.submit(lambda x: x, (np.arange(2),), nbytes=8).result()
        assert startup.status()["first_dispatch"] == t_first
        assert dp._first_dispatch_marked

    def test_file_input_counters_reach_the_metrics_tree(self, monkeypatch):
        fs = FileServer()
        fs.stats.reads_total = 7
        fs.stats.rounds_throttled[8] = 2
        monkeypatch.setattr(FileServer, "_instance", fs)
        text = exposition.render()
        assert ('loong_reads_total{category="file_input",'
                'component="file_server"} 7') in text
        assert ('loong_rounds_throttled_total{category="file_input",'
                'component="file_server",factor="8"} 2') in text

    def test_inflight_fraction_is_not_called_busy(self):
        plane = DevicePlane(budget_bytes=1 << 20)
        u = plane.utilization()
        assert "inflight_fraction" in u and "inflight_s" in u
        assert "busy_fraction" not in u and "busy_s" not in u


class _CountingPQM:
    def __init__(self, valid=True, accept=True):
        self.valid, self.accept, self.pushed = valid, accept, 0

    def is_valid_to_push(self, key):
        return self.valid

    def push_queue(self, key, group):
        self.pushed += self.accept
        return self.accept

    def get_queue(self, key):
        return None


def _file_server(tmp_path, pqm, content=b"a\nb\n"):
    fs = FileServer()
    path = tmp_path / "in.log"
    path.write_bytes(content)
    st = _ConfigState("t", FileDiscoveryConfig([str(path)]), queue_key=1,
                      tail_existing=True)
    fs._configs["t"] = st
    fs.process_queue_manager = pqm
    return fs


def _run_rounds(fs, seconds):
    fs._running = True
    th = threading.Thread(target=fs._run, daemon=True)
    th.start()
    time.sleep(seconds)
    fs._running = False
    fs._blocked_wake.set()
    th.join(timeout=5)
    assert not th.is_alive()


class TestFileInputCounters:
    @pytest.mark.parametrize("level,factor,other", [(0.8, 3, 8),
                                                    (0.95, 8, 3)])
    def test_governor_stretched_rounds_are_counted(self, tmp_path,
                                                   monkeypatch, level,
                                                   factor, other):
        monkeypatch.setenv("LOONG_DISABLE_INOTIFY", "1")
        fs = _file_server(tmp_path, _CountingPQM())
        fs.cpu_level_provider = lambda: level
        _run_rounds(fs, 0.25)
        s = fs.stats
        assert s.rounds_total >= 1
        assert s.rounds_throttled[factor] >= 1
        assert s.rounds_throttled[other] == 0
        assert s.throttle_sleep_s > 0.0
        assert s.rounds_throttled[factor] <= s.rounds_total

    def test_an_unthrottled_server_counts_rounds_only(self, tmp_path,
                                                     monkeypatch):
        monkeypatch.setenv("LOONG_DISABLE_INOTIFY", "1")
        fs = _file_server(tmp_path, _CountingPQM())
        fs.cpu_level_provider = lambda: 0.1
        _run_rounds(fs, 0.15)
        assert fs.stats.rounds_total >= 2
        assert fs.stats.rounds_throttled == {3: 0, 8: 0}
        assert fs.stats.throttle_sleep_s == 0.0

    @pytest.mark.parametrize("counter", ["reads_total", "read_bytes_total"])
    def test_reads_are_counted(self, tmp_path, counter):
        pqm = _CountingPQM()
        fs = _file_server(tmp_path, pqm)
        fs._round()
        assert pqm.pushed == 1
        assert getattr(fs.stats, counter) == {"reads_total": 1,
                                              "read_bytes_total": 4}[counter]
        assert fs.stats.reads_blocked_total == 0
        assert fs.stats.push_rejected_total == 0

    def test_full_queue_before_the_read(self, tmp_path):
        fs = _file_server(tmp_path, _CountingPQM(valid=False))
        fs._round()
        fs._round()
        assert fs.stats.reads_blocked_total == 2
        assert fs.stats.reads_total == 0

    def test_push_rejected_after_the_read(self, tmp_path):
        fs = _file_server(tmp_path, _CountingPQM(accept=False))
        fs._round()
        s = fs.stats
        assert s.push_rejected_total == 1 and s.reads_total == 1
        reader = fs._configs["t"].readers[str(tmp_path / "in.log")]
        assert reader.offset == 0               # rolled back

    def test_snapshot_has_the_documented_keys(self):
        assert list(FileInputStats().snapshot()) == [
            "rounds_total", "rounds_throttled_total",
            "throttle_sleep_seconds_total", "reads_total",
            "read_bytes_total", "reads_blocked_total",
            "push_rejected_total"]


class TestProgramNames:
    def test_watched_jit_names_the_module_after_the_family(self):
        import jax.numpy as jnp
        from loongcollector_tpu.ops.compile_watch import watched_jit

        def anything(x):
            return x + 1

        w = watched_jit(anything, "unit_family")
        lowered = w._fn.lower(jnp.zeros((4,), jnp.int32))
        assert "jit_loong_unit_family" in lowered.as_text()[:200]
        assert int(w(jnp.zeros((4,), jnp.int32))[0]) == 1


# ---------------------------------------------------------------------------
# work against waiting: cpu_s and tid on every span


def _spin(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


class TestCpuAndTid:
    def test_a_busy_span_is_mostly_cpu_and_never_more_than_its_wall(self):
        t = trace.enable()
        with trace.span("busy"):
            _spin(0.02)
        (sp,) = t.finished_spans()
        assert 0.018 <= sp.cpu_s <= sp.duration_s
        assert sp.attrs["cpu_s"] == sp.cpu_s
        assert sp.attrs["tid"] == sp.tid == threading.get_native_id()

    def test_a_sleeping_span_has_wall_and_next_to_no_cpu(self):
        t = trace.enable()
        with trace.span("asleep"):
            time.sleep(0.05)
        (sp,) = t.finished_spans()
        assert sp.duration_s >= 0.05 and sp.cpu_s < 0.01

    def test_a_span_ended_on_another_thread_has_no_cpu(self):
        t = trace.enable()
        sp = t.start_span("handed.over")
        th = threading.Thread(target=sp.end)
        th.start()
        th.join()
        (got,) = t.finished_spans()
        assert got.cpu_s is None and got.attrs["cpu_s"] is None
        assert got.tid == threading.get_native_id()     # where it started

    def test_a_stopwatch_says_so_at_its_source(self):
        t = trace.enable()
        sp = t.start_span("stopwatch", cpu=False)
        _spin(0.002)
        sp.end()
        assert t.finished_spans()[0].attrs["cpu_s"] is None
        assert t.child_or_sampled("s", "another", cpu=False)._start_cpu is None

    def test_a_span_its_caller_timed_takes_no_reading_of_its_own(
            self, monkeypatch):
        t = trace.enable()
        reads = []
        real = time.thread_time
        monkeypatch.setattr(time, "thread_time",
                            lambda: reads.append(1) or real())
        t.record_timed("s", "timed", time.perf_counter(), 0.001, None, 0.0005)
        assert reads == []                      # the clock is a system call
        with trace.span("self-timed"):
            pass
        assert len(reads) == 2

    def test_the_clocks_cost_and_step_are_on_the_status_page(self):
        trace.enable()
        doc = exposition.collect_status()["trace"]["cpu_clock"]
        assert doc == trace.tracer.cpu_clock()
        assert 0.0 < doc["cost_us"] < 1000.0
        assert doc["step_us"] is None or doc["step_us"] > 0.0

    def test_the_round_trip_is_a_stopwatch_and_its_legs_are_not(self):
        t = trace.enable()
        plane = DevicePlane(budget_bytes=1 << 20)
        plane.submit(lambda x: x + 1, (np.arange(8),), nbytes=64).result()
        by = {s.name: s for s in t.finished_spans()}
        assert by["device.roundtrip"].attrs["cpu_s"] is None
        for leg in ("device.submit", "device.wait", "device.d2h"):
            assert 0.0 <= by[leg].cpu_s <= by[leg].duration_s + 1e-5, leg
            assert by[leg].tid == by["device.roundtrip"].tid

    @pytest.mark.parametrize("in_flight", [True, False])
    def test_the_runners_root_span_is_a_stopwatch(self, in_flight):
        """`pipeline.process` stays open while its group's device work is
        in flight and the thread turns to other groups (or drains the lane
        ring under it): it takes no CPU reading, in flight or not."""
        class Pipeline:
            name = "p"

            def process_begin(self, groups):
                _spin(0.002)
                return (lambda: None) if in_flight else None

            def send(self, groups):
                pass

        class Manager:
            def find_pipeline_by_queue_key(self, key):
                return Pipeline()
        runner = ProcessorRunner(ProcessQueueManager(), Manager(),
                                 thread_count=1)
        t = trace.enable()
        pending = runner._dispatch_one(1, _one_group())
        assert (pending is not None) == in_flight
        while pending is not None:
            pending = runner._complete(pending)
        (root,) = [s for s in t.finished_spans()
                   if s.name == "pipeline.process"]
        assert root.attrs["cpu_s"] is None and root.duration_s >= 0.002
        assert root.attrs["tid"] == threading.get_native_id()

    def test_stage_spans_keep_their_cpu_under_a_stopwatch_root(self):
        by, root, _tl = _staged_roundtrip(False)
        for name in ("processor.processor_stub_device.dispatch",
                     "processor.processor_stub_device.complete"):
            (sp,) = by[name]
            assert sp.cpu_s is not None and sp.cpu_s <= sp.duration_s + 1e-5
            assert sp.tid == root.tid

    @pytest.mark.parametrize("cpu_s", [0.003, None])
    def test_close_at_and_record_timed_carry_the_callers_cpu(self, cpu_s):
        t = trace.enable()
        t0 = time.perf_counter()
        t.start_span("a").close_at(t0, 0.01, cpu_s=cpu_s)
        t.record_timed("s", "b", t0, 0.01, {"k": 1}, cpu_s)
        a, b = t.finished_spans()
        assert a.cpu_s == b.cpu_s == cpu_s
        assert b.attrs == {"k": 1, "cpu_s": cpu_s,
                           "tid": threading.get_native_id()}

    def test_a_collection_is_the_cpu_of_the_thread_that_tripped_it(self):
        t = trace.enable()
        done = []

        def collect():
            done.append(threading.get_native_id())
            gc.collect()
        th = threading.Thread(target=collect)
        th.start()
        th.join()
        t.start_span("x").end()            # folds it, on this thread
        g = [s for s in t.finished_spans() if s.name == "runtime.gc"][-1]
        assert g.tid == done[0] != threading.get_native_id()
        assert 0.0 < g.cpu_s <= g.duration_s + 1e-5

    def test_cpu_and_tid_do_not_change_the_structure(self):
        def run(spin):
            t = trace.enable()
            with trace.span("a", k=1):
                _spin(spin)
            th = threading.Thread(target=lambda: t.start_span("b").end())
            th.start()
            th.join()
            out = t.structure_bytes()
            trace.disable()
            return out
        assert run(0.0) == run(0.003)
        assert b"cpu_s" not in run(0.0) and b"tid" not in run(0.0)

    def test_the_exporters_carry_both(self):
        from loongcollector_tpu.trace.export import (chrome_trace,
                                                     traces_to_group)
        t = trace.enable()
        with trace.span("a"):
            pass
        (ev,) = [e for e in chrome_trace(t)["traceEvents"]
                 if e.get("name") == "a"]
        assert ev["args"]["tid"] == threading.get_native_id()
        assert ev["args"]["cpu_s"] >= 0.0
        group = traces_to_group(*t.drain())
        attrs = json.loads(bytes(group.events[0].get_content(b"attrs")))
        assert attrs["tid"] == threading.get_native_id() \
            and attrs["cpu_s"] >= 0.0
