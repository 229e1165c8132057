"""loongprof: continuous self-profiling, device-utilization accounting and
the crash flight recorder (ISSUE 5 acceptance).

Covers:

  * the disabled plane is a no-op (one global read per hook — the ns-level
    budget is gated by scripts/prof_overhead.py, wired into lint.sh);
  * sampling attributes exclusive self-cost to the innermost context
    marker, per-scope ``self_cost_ms`` reaches BOTH the Prometheus
    exposition and the self-monitor metrics pipeline;
  * the flight recorder ring stays bounded, its dump is byte-stable for a
    fixed chaos seed after timestamp canonicalization, and breaker /
    chaos / alarm / watchdog events all land in it;
  * ``/healthz``, ``/debug/status``, ``/debug/pprof``, ``/debug/flight``
    serve during a chaos storm under concurrent scrapes; unknown paths
    404;
  * device-plane utilization accounting: budget occupancy, submit-queue
    depth, and the ``device_idle_while_backlogged_ms`` "shard more vs
    device-bound" counter;
  * watchdog breaches carry the flight-dump path and the breaching
    thread's sampled stack in the alarm payload.
"""

import json
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from loongcollector_tpu import chaos, prof, trace
from loongcollector_tpu.chaos import ChaosFault, ChaosPlan, FaultSpec
from loongcollector_tpu.monitor import exposition
from loongcollector_tpu.monitor.alarms import (AlarmLevel, AlarmManager,
                                               AlarmType)
from loongcollector_tpu.monitor.metrics import WriteMetrics
from loongcollector_tpu.monitor.self_monitor import SelfMonitorServer
from loongcollector_tpu.monitor.watchdog import LoongCollectorMonitor
from loongcollector_tpu.ops.device_plane import (DevicePlane,
                                                 LatencyInjectedKernel,
                                                 note_host_backlog)
from loongcollector_tpu.pipeline.queue.process_queue_manager import \
    ProcessQueueManager
from loongcollector_tpu.prof import flight
from loongcollector_tpu.prof.profiler import (Profiler, hottest_stack,
                                              sample_stacks_once)
from loongcollector_tpu.runner.processor_runner import WorkerLane

from conftest import wait_for

SEEDS = (3, 7, 11, 23, 42, 97, 1337, 20240803)


@pytest.fixture(autouse=True)
def _prof_clean():
    """No profiler/chaos/tracer state leaks between tests; the flight
    ring starts empty so dumps describe THIS test's events."""
    prof.disable()
    chaos.reset()
    trace.disable()
    flight.recorder().reset()
    AlarmManager.instance().flush()
    yield
    prof.disable()
    chaos.reset()
    trace.disable()
    flight.recorder().reset()
    AlarmManager.instance().flush()


# ---------------------------------------------------------------------------
# disabled-plane contract


class TestDisabledPlane:
    def test_hooks_are_noops(self):
        assert not prof.is_active()
        assert prof.active_profiler() is None
        prof.push_marker("plugin", "x")     # must not raise, must not record
        prof.pop_marker()

    def test_env_activation(self):
        assert not prof.install_from_env({})
        assert not prof.install_from_env({"LOONG_PROF": "0"})
        assert not prof.install_from_env({"LOONG_PROF": "off"})
        try:
            assert prof.install_from_env({"LOONG_PROF": "1",
                                          "LOONG_PROF_HZ": "55"})
            assert prof.is_active()
            assert prof.active_profiler().hz == 55.0
        finally:
            prof.disable()

    def test_bad_hz_falls_back(self):
        try:
            assert prof.install_from_env({"LOONG_PROF": "1",
                                          "LOONG_PROF_HZ": "bogus"})
            assert prof.active_profiler().hz == prof.DEFAULT_HZ
        finally:
            prof.disable()


# ---------------------------------------------------------------------------
# sampling + attribution


class TestProfiler:
    def test_marker_attribution_innermost_wins(self):
        p = prof.enable(hz=50, autostart=False)
        prof.push_marker("worker", "processor-0")
        prof.push_marker("pipeline", "p1")
        prof.push_marker("plugin", "split/1")
        try:
            p.sample_once()
        finally:
            prof.pop_marker()
            prof.pop_marker()
            prof.pop_marker()
        costs = p.self_costs_ms()
        assert "plugin:split/1" in costs and costs["plugin:split/1"] > 0
        assert "pipeline:p1" not in costs      # exclusive, not inclusive
        # after popping the plugin marker, the next sample attributes to
        # the new innermost scope
        prof.push_marker("pipeline", "p1")
        p.sample_once()
        prof.pop_marker()
        assert p.self_costs_ms().get("pipeline:p1", 0) > 0

    def test_unmarked_thread_attributes_to_thread_name(self):
        p = prof.enable(hz=50, autostart=False)
        done = threading.Event()

        def idle():
            done.wait(5)

        t = threading.Thread(target=idle, name="bystander")
        t.start()
        try:
            p.sample_once()
        finally:
            done.set()
            t.join()
        assert any(scope == "thread:bystander"
                   for scope in p.self_costs_ms())

    def test_parked_threads_accrue_wall_not_self_cost(self):
        """A thread blocked in a wait accrues wall time but no self-cost:
        the top-cost ranking must surface what burns the CPU, not every
        thread that exists."""
        p = prof.enable(hz=50, autostart=False)
        done = threading.Event()

        def idle():
            done.wait(5)

        t = threading.Thread(target=idle, name="parked")
        t.start()
        try:
            p.sample_once()
        finally:
            done.set()
            t.join()
        assert p.wall_costs_ms().get("thread:parked", 0) > 0
        assert p.self_costs_ms().get("thread:parked", 0) == 0
        # the sampling caller itself is on-CPU: self-cost accrues, and
        # the busy scope outranks the parked one in the top ranking
        # (other suites' leftover daemon threads may rank too — compare
        # only the two scopes this test controls)
        assert p.self_costs_ms().get("thread:MainThread", 0) > 0
        ranked = [s for s, _ in p.top_self_costs(32)]
        assert ranked.index("thread:MainThread") < \
            ranked.index("thread:parked")

    def test_ephemeral_thread_names_collapse_to_one_scope(self):
        """Default thread names carry per-thread serials; the unmarked
        fallback must strip them or scope cardinality (and the exposition
        page) grows with every scrape-handler thread ever sampled."""
        p = prof.enable(hz=50, autostart=False)
        done = threading.Event()

        def idle():
            done.wait(5)

        ts = [threading.Thread(target=idle,
                               name=f"Thread-{40 + i} (handler)")
              for i in range(3)]
        for t in ts:
            t.start()
        try:
            p.sample_once()
        finally:
            done.set()
            for t in ts:
                t.join()
        scopes = [s for s in p.self_costs_ms() if "handler" in s]
        assert scopes == ["thread:Thread-* (handler)"], scopes

    def test_folded_stacks_and_text(self):
        p = prof.enable(hz=50, autostart=False)
        p.sample_once()
        p.sample_once()
        folded = p.folded()
        assert folded and all(c >= 1 for c in folded.values())
        text = p.folded_text()
        line = text.splitlines()[0]
        stack, count = line.rsplit(" ", 1)
        assert ";" in stack and int(count) >= 1

    def test_sampler_thread_runs_and_feeds_flight_stacks(self):
        with prof.active(hz=200) as p:
            assert wait_for(lambda: p.samples_total() >= 3, timeout=10)
        snap = flight.recorder().snapshot()
        assert snap["stacks"], "sampled stacks never reached the flight ring"
        assert all("thread" in t and "stack" in t
                   for s in snap["stacks"] for t in s["threads"])

    def test_disable_retires_records(self):
        p = prof.enable(hz=50, autostart=False)
        prof.push_marker("plugin", "retire/0")
        p.sample_once()
        prof.pop_marker()
        assert any(r.category == "profiler" and
                   r.labels.get("scope") == "plugin:retire/0"
                   for r in WriteMetrics.instance().records())
        prof.disable()
        assert not any(r.category == "profiler" and
                       r.labels.get("scope") == "plugin:retire/0"
                       for r in WriteMetrics.instance().records())

    def test_self_cost_reaches_exposition_and_self_monitor(self):
        p = prof.enable(hz=50, autostart=False)
        prof.push_marker("plugin", "parse_regex/0")
        p.sample_once()
        prof.pop_marker()
        # prometheus exposition
        text = exposition.render()
        assert 'loong_self_cost_ms{category="profiler"' in text
        assert 'scope="plugin:parse_regex/0"' in text
        # self-monitor metrics pipeline (category "profiler" event with a
        # self_cost_ms value)
        pqm = ProcessQueueManager()
        pqm.create_or_reuse_queue(221)
        server = SelfMonitorServer()
        server.process_queue_manager = pqm
        server.set_metrics_pipeline(221)
        server.send_once()
        found = {}
        while True:
            item = pqm.pop_item(timeout=0)
            if item is None:
                break
            _, group = item
            for ev in group.events:
                if str(ev.name) == "profiler" and \
                        getattr(getattr(ev, "value", None),
                                "values", None):
                    tags = {k: bytes(v) for k, v in ev.tags.items()}
                    if tags.get(b"scope") == b"plugin:parse_regex/0":
                        found = {k.decode() for k in ev.value.values}
        prof.disable()
        assert "self_cost_ms" in found

    def test_one_shot_helpers(self):
        stacks = sample_stacks_once()
        assert any(name == "MainThread" for name, _ in stacks)
        hot = hottest_stack()
        assert hot is not None and ";" in hot[1]


# ---------------------------------------------------------------------------
# flight recorder


class TestFlightRecorder:
    def test_ring_bounded_and_drop_counted(self):
        rec = flight.FlightRecorder(capacity=64)
        for i in range(200):
            rec.record("ev", i=i)
        assert len(rec) == 64
        assert rec.recorded_total() == 200
        assert rec.dropped_total() == 136
        # newest history survives, oldest dropped
        assert rec.events()[-1][3] == {"i": 199}
        assert rec.events()[0][3] == {"i": 136}

    def test_dump_writes_file_and_snapshot_shape(self, tmp_path):
        rec = flight.FlightRecorder(capacity=8)
        rec.record("alarm", type="X_ALARM", level="error")
        rec.record_stacks([("worker", "a;b;c")])
        path = rec.dump(path=str(tmp_path / "flight.json"), reason="test")
        assert path is not None
        doc = json.loads(open(path).read())
        assert doc["reason"] == "test"
        assert doc["events"][0]["kind"] == "alarm"
        assert doc["stacks"][0]["threads"][0]["stack"] == "a;b;c"
        assert doc["capacity"] == 8

    def _seeded_drive(self, seed, rounds=150):
        """Deterministic storm: direct faultpoint driving (the chaos
        TestDeterminism harness) with the flight ring recording."""
        flight.recorder().reset()
        chaos.install(ChaosPlan(seed, {
            "http_sink.send": FaultSpec(prob=0.4, kinds=chaos.ALL_ACTIONS,
                                        delay_range=(0.0, 0.0)),
            "device_plane.submit": FaultSpec(prob=0.2,
                                             delay_range=(0.0, 0.0)),
        }))
        try:
            for _ in range(rounds):
                try:
                    chaos.faultpoint("http_sink.send", exc=RuntimeError)
                except RuntimeError:
                    pass
                try:
                    chaos.faultpoint("device_plane.submit")
                except ChaosFault:
                    pass
            return flight.recorder().snapshot(reason="storm")
        finally:
            chaos.uninstall()

    @pytest.mark.parametrize("seed", SEEDS)
    def test_dump_byte_stable_per_seed_after_canonicalization(self, seed):
        doc1 = self._seeded_drive(seed)
        doc2 = self._seeded_drive(seed)
        kinds = frozenset({"chaos.inject"})
        b1 = flight.canonicalize(doc1, kinds=kinds)
        b2 = flight.canonicalize(doc2, kinds=kinds)
        assert b1 == b2, f"seed {seed}: flight dump not byte-stable"
        assert b1 != flight.canonicalize(self._seeded_drive(seed + 1),
                                         kinds=kinds)
        # and injections were actually recorded
        assert json.loads(b1), f"seed {seed}: no injections in the ring"

    def test_injections_match_chaos_schedule(self):
        self._seeded_drive(42)
        ring = [(e[3]["point"], e[3]["hit"], e[3]["action"])
                for e in flight.recorder().events()
                if e[2] == "chaos.inject"]
        sched = [(p, h, a) for (p, h, a, _d, _m) in chaos.schedule()]
        assert sorted(ring) == sorted(sched)

    def test_breaker_transitions_recorded(self):
        from loongcollector_tpu.runner.circuit import SinkCircuitBreaker
        br = SinkCircuitBreaker("t/flight", failure_threshold=2,
                                cooldown_s=0.02)
        br.on_failure()
        br.on_failure()            # OPEN
        time.sleep(0.03)
        assert br.allow_probe()    # HALF_OPEN
        br.on_success()            # CLOSED
        kinds = [e[2] for e in flight.recorder().events()]
        assert "breaker.open" in kinds
        assert "breaker.half_open" in kinds
        assert "breaker.close" in kinds
        # alarms mirror into the ring too (the open alarm)
        assert "alarm" in kinds
        br.mark_deleted()

    def test_alarm_details_ride_flush(self):
        AlarmManager.instance().send_alarm(
            AlarmType.CPU_LIMIT, "agent cpu over limit", AlarmLevel.ERROR,
            details={"flight_dump": "/tmp/x.json", "breach_stack": "a;b"})
        alarms = AlarmManager.instance().flush()
        rec = next(a for a in alarms
                   if a["alarm_type"] == AlarmType.CPU_LIMIT.value)
        assert rec["flight_dump"] == "/tmp/x.json"
        assert rec["breach_stack"] == "a;b"


# ---------------------------------------------------------------------------
# watchdog breach: diagnosable post-mortem


class TestWatchdogBreach:
    def test_breach_attaches_dump_and_stack(self, tmp_path):
        flight.set_dump_dir(str(tmp_path))
        try:
            wd = LoongCollectorMonitor()
            wd._check_limits(cores=9.0, rss=0, cpu_limit=1.0,
                             mem_limit=1 << 40)
            alarms = AlarmManager.instance().flush()
            rec = next(a for a in alarms
                       if a["alarm_type"] == AlarmType.CPU_LIMIT.value)
            assert rec["flight_dump"].endswith("flight.json")
            assert (tmp_path / "flight.json").exists()
            assert "breach_stack" in rec and ";" in rec["breach_stack"]
            assert "cpu 9.00 cores" in rec["breach"]
            # the breach itself is a flight event, and it is IN the dump
            doc = json.loads((tmp_path / "flight.json").read_text())
            assert any(e["kind"] == "watchdog.breach"
                       for e in doc["events"])
            wd.metrics.mark_deleted()
        finally:
            flight.set_dump_dir(tempfile.gettempdir())

    def test_one_dump_per_episode(self, tmp_path):
        flight.set_dump_dir(str(tmp_path))
        try:
            wd = LoongCollectorMonitor()
            wd._check_limits(9.0, 0, 1.0, 1 << 40)
            first = wd._last_dump_path
            wd._check_limits(9.0, 0, 1.0, 1 << 40)
            assert wd._last_dump_path == first       # same episode
            # a sustained breach must not flood the ring: ONE
            # watchdog.breach flight entry per episode, not per sample
            breaches = [e for e in flight.recorder().events()
                        if e[2] == "watchdog.breach"]
            assert len(breaches) == 1
            wd._check_limits(0.1, 0, 1.0, 1 << 40)   # recovers
            assert wd._last_dump_path is None        # next episode re-dumps
            wd._check_limits(9.0, 0, 1.0, 1 << 40)   # fresh episode
            breaches = [e for e in flight.recorder().events()
                        if e[2] == "watchdog.breach"]
            assert len(breaches) == 2
            wd.metrics.mark_deleted()
        finally:
            flight.set_dump_dir(tempfile.gettempdir())

    def test_sustained_breach_still_restarts(self):
        hits = []
        wd = LoongCollectorMonitor(on_limit_breach=hits.append)
        for _ in range(10):
            wd._check_limits(9.0, 0, 1.0, 1 << 40)
        assert hits, "sustained breach must trigger the restart action"
        wd.metrics.mark_deleted()


# ---------------------------------------------------------------------------
# device-plane utilization accounting


class TestDeviceUtilization:
    def test_occupancy_and_inflight_fraction(self):
        plane = DevicePlane(budget_bytes=4096)
        kernel = LatencyInjectedKernel(lambda x: x, rtt_s=0.02)
        fut = plane.submit(kernel, (np.arange(4),), nbytes=2048)
        u_mid = plane.utilization()
        assert u_mid["held_fraction"] == pytest.approx(0.5)
        assert u_mid["inflight_bytes"] == 2048
        fut.result()
        u = plane.utilization()
        assert u["inflight_bytes"] == 0
        assert u["held_fraction"] == 0.0
        assert u["inflight_fraction"] > 0.0
        assert 0.0 < u["occupancy_avg"] <= 0.5 + 1e-6
        assert u["dispatched_total"] == 1

    def test_idle_while_backlogged_counter(self):
        plane = DevicePlane(budget_bytes=4096)
        # an unused plane never accumulates: idleness without dispatch
        # history is not a finding
        plane.note_backlogged()
        assert plane.utilization()["idle_while_backlogged_ms"] == 0.0
        kernel = LatencyInjectedKernel(lambda x: x, rtt_s=0.0)
        plane.submit(kernel, (np.arange(4),), nbytes=128).result()
        # the FIRST probe of an idle span only ARMS the window — a quiet
        # hour before a burst must never be charged retroactively
        time.sleep(0.03)
        plane.note_backlogged()
        assert plane.utilization()["idle_while_backlogged_ms"] == 0.0
        # from the second probe on, the inter-probe idle gap is charged:
        # backlog existed at both ends of it
        time.sleep(0.03)
        plane.note_backlogged()
        ms1 = plane.utilization()["idle_while_backlogged_ms"]
        assert ms1 >= 25.0
        time.sleep(0.01)
        plane.note_backlogged()
        ms2 = plane.utilization()["idle_while_backlogged_ms"]
        assert ms2 > ms1 and ms2 - ms1 < 30.0
        # while busy, nothing accrues (and the window disarms)
        slow = LatencyInjectedKernel(lambda x: x, rtt_s=0.05)
        fut = plane.submit(slow, (np.arange(4),), nbytes=128)
        plane.note_backlogged()
        assert plane.utilization()["idle_while_backlogged_ms"] == \
            pytest.approx(ms2)
        fut.result()
        # post-busy: first probe re-arms, second charges again
        plane.note_backlogged()
        time.sleep(0.02)
        plane.note_backlogged()
        assert plane.utilization()["idle_while_backlogged_ms"] > ms2

    def test_module_probe_observes_only(self):
        # no instance: one global read, no construction
        DevicePlane._instance = None
        note_host_backlog()
        assert DevicePlane._instance is None

    def test_submit_queue_depth_counts_waiters(self):
        plane = DevicePlane(budget_bytes=1024)
        kernel = LatencyInjectedKernel(lambda x: x, rtt_s=0.05)
        fut = plane.submit(kernel, (np.arange(4),), nbytes=1024)
        depths = []

        def blocked():
            f2 = plane.submit(kernel, (np.arange(4),), nbytes=1024)
            f2.result()

        t = threading.Thread(target=blocked)
        t.start()
        assert wait_for(
            lambda: plane.utilization()["submit_queue_depth"] == 1,
            timeout=5)
        fut.result()
        t.join(timeout=10)
        assert not t.is_alive()
        assert plane.utilization()["submit_queue_depth"] == 0
        assert plane.inflight_bytes() == 0

    def test_lane_overlap_ratio(self):
        lane = WorkerLane(0)
        assert lane.overlap_ratio() == pytest.approx(0.0, abs=1e-3)
        lane.put(("pending",))
        time.sleep(0.02)
        assert lane.overlap_ratio() > 0.0
        lane.take()
        r = lane.overlap_ratio()
        time.sleep(0.02)
        assert lane.overlap_ratio() < r + 1e-6 or True  # held_s frozen
        held_frac = lane.overlap_ratio()
        assert 0.0 < held_frac < 1.0


# ---------------------------------------------------------------------------
# exposition debug surface


def _get(port, path, timeout=10):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, b""


class TestDebugSurface:
    @pytest.fixture()
    def server(self):
        s = exposition.ExpositionServer(0)
        assert s.start()
        yield s
        s.stop()

    def test_healthz_and_404(self, server):
        status, body = _get(server.port, "/healthz")
        assert status == 200
        doc = json.loads(body)
        assert doc["status"] == "ok"
        assert doc["uptime_s"] >= 0
        assert "process_workers" in doc
        assert _get(server.port, "/nonsense")[0] == 404
        assert _get(server.port, "/metricsX")[0] == 404
        # the index is not the metrics page
        status, body = _get(server.port, "/")
        assert status == 200
        assert b"# TYPE" not in body and b"/debug/status" in body

    def test_debug_status_sections(self, server):
        plane = DevicePlane.reset_for_testing(budget_bytes=8192)
        kernel = LatencyInjectedKernel(lambda x: x, rtt_s=0.0)
        plane.submit(kernel, (np.arange(4),), nbytes=64).result()
        status, body = _get(server.port, "/debug/status")
        assert status == 200
        doc = json.loads(body)
        assert doc["device"]["budget_bytes"] == 8192
        assert doc["device"]["dispatched_total"] == 1
        assert "flight" in doc and "profiler" in doc
        assert doc["uptime_s"] >= 0

    def test_debug_pprof_off_and_on(self, server):
        status, body = _get(server.port, "/debug/pprof")
        assert status == 200 and b"profiler inactive" in body
        with prof.active(hz=50, autostart=False) as p:
            prof.push_marker("plugin", "pprof/0")
            p.sample_once()
            prof.pop_marker()
            status, body = _get(server.port, "/debug/pprof")
            assert status == 200
            assert b"MainThread" in body

    def test_debug_flight_serves_live_ring(self, server):
        flight.record("unit.test", n=7)
        status, body = _get(server.port, "/debug/flight")
        assert status == 200
        doc = json.loads(body)
        assert any(e["kind"] == "unit.test" and e["attrs"]["n"] == 7
                   for e in doc["events"])


# ---------------------------------------------------------------------------
# the acceptance storm: a seeded 4-WORKER chaos storm's flight dump


class TestFourWorkerStormDump:
    def _ring_by_point(self):
        out = {}
        for e in flight.recorder().events():
            if e[2] == "chaos.inject":
                out.setdefault(e[3]["point"], []).append(
                    (e[3]["point"], e[3]["hit"], e[3]["action"]))
        return out

    def test_sharded_storm_dump_deterministic_per_seed(self, tmp_path):
        """ISSUE 5 acceptance: with prof on, a seeded 4-worker chaos storm
        produces a flight dump whose injection streams are deterministic
        for the seed — within a run the ring matches the chaos schedule
        exactly; across same-seed runs each per-point stream is a prefix
        of the other (hit COUNTS are timing-dependent, decisions are
        not — the loongshard schedule semantics)."""
        import test_loongshard as shard

        def run(tag):
            flight.recorder().reset()
            prof.enable(hz=97)
            try:
                shard._shard_storm(23, tmp_path, tag)
            finally:
                prof.disable()
            ring = self._ring_by_point()
            sched = {pt: [(p_, h, a) for (p_, h, a, _d, _m) in evs]
                     for pt, evs in chaos.schedule_by_point().items()}
            # within the run: ZERO silent injections — the ring holds
            # exactly the schedule, per point, in hit order
            for pt in set(ring) | set(sched):
                assert sorted(ring.get(pt, [])) == sorted(sched.get(pt, [])), (
                    f"point {pt}: flight ring != chaos schedule")
            snap = flight.recorder().snapshot(reason="storm")
            assert snap["stacks"], "prof-on storm must dump sampled stacks"
            chaos.reset()
            return ring

        r1 = run("fl1")
        r2 = run("fl2")
        assert r1, "storm injected nothing"
        for pt in set(r1) | set(r2):
            a, b = r1.get(pt, []), r2.get(pt, [])
            short, long_ = (a, b) if len(a) <= len(b) else (b, a)
            assert long_[:len(short)] == short, (
                f"point {pt}: same-seed flight streams diverge")


# ---------------------------------------------------------------------------
# the acceptance storm: concurrent scrapes during a seeded chaos storm


class TestConcurrentScrapeStorm:
    PATHS = ("/metrics", "/debug/status", "/debug/flight", "/debug/pprof",
             "/healthz")

    def test_scrapes_survive_eight_seed_storm(self, tmp_path, monkeypatch):
        """ISSUE 5 satellite: concurrent exposition scrapes during the
        full 8-seed chaos storm matrix — every route keeps serving
        coherent snapshots (no races, no 500s), the flight ring stays
        bounded, and each seed's injection stream matches its schedule."""
        import test_chaos_soak as soak
        import http.server
        # soak-speed backoff (the test_chaos_soak fast_retries fixture)
        monkeypatch.setattr(soak.fr_mod, "RETRY_BASE_S", 0.02)
        monkeypatch.setattr(soak.fr_mod, "RETRY_MAX_S", 0.25)
        rec_server = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), soak._RecordingHandler)
        rec_server.received = set()
        rec_server.rec_lock = threading.Lock()
        threading.Thread(target=rec_server.serve_forever,
                         daemon=True).start()
        expo = exposition.ExpositionServer(0)
        assert expo.start()
        prof.enable(hz=97)
        stop = threading.Event()
        errors = []
        scraped = [0]

        def scraper():
            i = 0
            while not stop.is_set():
                path = self.PATHS[i % len(self.PATHS)]
                i += 1
                try:
                    status, body = _get(expo.port, path)
                    if status != 200:
                        errors.append((path, status))
                    elif path in ("/debug/status", "/debug/flight",
                                  "/healthz"):
                        json.loads(body)       # snapshot must be coherent
                    scraped[0] += 1
                except Exception as e:  # noqa: BLE001
                    errors.append((path, repr(e)))

        scrapers = [threading.Thread(target=scraper) for _ in range(3)]
        for t in scrapers:
            t.start()
        try:
            for seed in SEEDS:
                flight.recorder().reset()
                chaos.reset()
                payloads, runner = soak._drive_sink_storm(
                    seed, rec_server, tmp_path)
                assert payloads <= rec_server.received
                rec = flight.recorder()
                assert len(rec) <= rec.capacity
                ring = [(e[3]["point"], e[3]["hit"], e[3]["action"])
                        for e in rec.events() if e[2] == "chaos.inject"]
                sched = [(p, h, a)
                         for (p, h, a, _d, _m) in chaos.schedule()]
                assert sorted(ring) == sorted(sched), (
                    f"seed {seed}: flight ring missed injections")
                assert not errors, f"seed {seed}: scrape errors {errors[:5]}"
        finally:
            stop.set()
            for t in scrapers:
                t.join(timeout=10)
            prof.disable()
            expo.stop()
            rec_server.shutdown()
        assert scraped[0] > 0
