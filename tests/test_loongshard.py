"""loongshard: sharded multi-worker processing plane (ISSUE 4).

Covers the tentpole invariants:
  * affinity sharding is deterministic (CRC32, PYTHONHASHSEED-proof) and
    groups of one (pipeline, source) always land on one worker;
  * per-source ordering survives thread_count=4 — a test that FAILS if
    shards reorder or drop;
  * thread_count wiring: LOONG_PROCESS_THREADS env over flag, validated
    >= 1, surfaced as the process_workers gauge;
  * WorkerLane budget-relief completes the owning worker's in-flight
    group exactly once, even racing the worker loop;
  * seeded chaos storms with multi-worker shards: zero loss,
    DevicePlane.inflight == 0 post-storm, per-source delivery order and
    the chaos schedule deterministic across same-seed re-runs.
"""

import json
import os
import threading
import time

import pytest

from loongcollector_tpu import chaos, trace
from loongcollector_tpu.chaos import ChaosPlan, FaultSpec
from loongcollector_tpu.models import (EventGroupMetaKey, PipelineEventGroup,
                                       SourceBuffer)
from loongcollector_tpu.monitor import ledger
from loongcollector_tpu.monitor.alarms import AlarmManager, AlarmType
from loongcollector_tpu.ops.device_plane import DevicePlane
from loongcollector_tpu.pipeline.pipeline_manager import (
    CollectionPipelineManager, ConfigDiff)
from loongcollector_tpu.pipeline.queue.process_queue_manager import \
    ProcessQueueManager
from loongcollector_tpu.pipeline.queue.sender_queue import SenderQueueManager
from loongcollector_tpu.runner.processor_runner import (ProcessorRunner,
                                                        WorkerLane,
                                                        group_source_id,
                                                        resolve_thread_count,
                                                        shard_of)

from conftest import wait_for


@pytest.fixture(autouse=True)
def _clean():
    chaos.reset()
    trace.disable()
    ledger.disable()
    yield
    chaos.reset()
    trace.disable()
    ledger.disable()
    AlarmManager.instance().flush()


def _group(payload: bytes, source: bytes = b"", path: str = "",
           inode: str = "") -> PipelineEventGroup:
    sb = SourceBuffer(len(payload) + 64)
    g = PipelineEventGroup(sb)
    g.add_raw_event(1).set_content(sb.copy_string(payload))
    if source:
        g.set_tag(b"__source__", source)
    if path:
        g.set_metadata(EventGroupMetaKey.LOG_FILE_PATH, path)
    if inode:
        g.set_metadata(EventGroupMetaKey.LOG_FILE_INODE, inode)
    return g


class TestShardAffinity:
    def test_deterministic_across_processes(self):
        # CRC32 of the source seeded with the key: stable constants, not
        # Python hash() (which is salted per process)
        assert shard_of(17, b"srcA", 4) == shard_of(17, b"srcA", 4)
        assert shard_of(17, b"srcA", 4) == 0      # crc32(b"srcA", 17) % 4
        assert shard_of(17, b"srcB", 4) == 2
        assert shard_of(99, b"srcA", 4) == 3      # key seeds the hash

    def test_single_worker_short_circuits(self):
        assert shard_of(1, b"anything", 1) == 0
        assert shard_of(1, None, 1) == 0

    def test_spread_over_workers(self):
        shards = {shard_of(5, b"src%d" % i, 4) for i in range(64)}
        assert shards == {0, 1, 2, 3}

    def test_source_identity_prefers_tag(self):
        g = _group(b"x", source=b"udp", path="/var/log/a.log", inode="77")
        assert group_source_id(g) == b"udp"

    def test_source_identity_falls_back_to_file(self):
        g = _group(b"x", path="/var/log/a.log", inode="77")
        assert group_source_id(g) == b"/var/log/a.log:77"
        g2 = _group(b"x", path="/var/log/a.log")
        assert group_source_id(g2) == b"/var/log/a.log"

    def test_unkeyed_groups_share_a_shard(self):
        g = _group(b"x")
        assert group_source_id(g) is None
        assert shard_of(3, group_source_id(g), 4) \
            == shard_of(3, group_source_id(_group(b"y")), 4)


class TestThreadCountConfig:
    def test_env_wins(self):
        assert resolve_thread_count({"LOONG_PROCESS_THREADS": "3"}) == 3

    def test_env_invalid_falls_back_to_flag(self):
        from loongcollector_tpu.utils import flags
        flag = flags.get_flag("process_thread_count")
        assert resolve_thread_count({"LOONG_PROCESS_THREADS": "zero"}) \
            == flag
        assert resolve_thread_count({"LOONG_PROCESS_THREADS": "0"}) == flag
        assert resolve_thread_count({"LOONG_PROCESS_THREADS": "-2"}) == flag

    def test_default_flag_is_multi_worker(self):
        from loongcollector_tpu.utils import flags
        assert flags.get_flag("process_thread_count") >= 2

    def test_runner_validates_floor(self):
        r = ProcessorRunner(ProcessQueueManager(), None, thread_count=0)
        assert r.thread_count == 1
        r.metrics.mark_deleted()

    def test_workers_gauge_reports_active_count(self):
        pqm = ProcessQueueManager()
        r = ProcessorRunner(pqm, None, thread_count=4)
        r.init()
        try:
            assert r.workers_gauge.value == 4
            assert len([t for t in threading.enumerate()
                        if t.name.startswith("processor-")]) >= 4
            # the exposition endpoint serves the active worker count (the
            # satellite contract: operators see the live shard count)
            from loongcollector_tpu.monitor import exposition
            text = exposition.render()
            assert 'loong_process_workers{category="runner",' \
                   'runner="processor"} 4' in text
        finally:
            r.stop()


class TestWorkerLane:
    def _pending(self, done):
        class _P:
            name = "p"

            def send(self, groups):
                pass
        return (_P(), [], lambda: done.append(1), None, time.perf_counter(),
                "lane0")

    def test_relief_completes_owning_lane_once(self):
        r = ProcessorRunner(ProcessQueueManager(), None, thread_count=2)
        lane = WorkerLane(0)
        done = []
        lane.put(self._pending(done))
        relief = r._make_relief(lane)
        assert relief() is True
        assert done == [1]
        assert relief() is False, "a lane's group completes exactly once"
        r.metrics.mark_deleted()

    def test_take_is_single_winner_under_race(self):
        lane = WorkerLane(1)
        lane.put(("sentinel",))
        got = []
        barrier = threading.Barrier(8)

        def taker():
            barrier.wait()
            p = lane.take()
            if p is not None:
                got.append(p)
        ts = [threading.Thread(target=taker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert got == [("sentinel",)]

    def test_lane_ring_rejects_overfill(self):
        # loongstream: the lane is a FIFO ring of capacity depth-1
        lane = WorkerLane(2, depth=3)
        assert lane.capacity == 2
        lane.put(("a",))
        lane.put(("b",))
        assert lane.full()
        with pytest.raises(AssertionError):
            lane.put(("c",))
        assert lane.take() == ("a",), "ring advance must be FIFO"
        assert lane.take() == ("b",)
        lane.put(None)          # no-op
        assert lane.take() is None

    def test_lane_depth_one_is_synchronous(self):
        # depth=1 (LOONG_STREAM_DEPTH=1) degenerates to capacity 1 — the
        # pre-stream single-slot behaviour
        lane = WorkerLane(0, depth=1)
        assert lane.capacity == 1
        lane.put(("a",))
        with pytest.raises(AssertionError):
            lane.put(("b",))
        assert lane.take() == ("a",)

    def test_lane_oldest_age_tracks_ring_head(self):
        lane = WorkerLane(1, depth=3)
        assert lane.oldest_age() is None
        lane.put(("a",))
        time.sleep(0.25)
        lane.put(("b",))
        age_a = lane.oldest_age()
        assert age_a is not None and age_a >= 0.25
        lane.take()
        age_b = lane.oldest_age()
        # generous bound: "b" was just enqueued — only a pathological
        # scheduler stall approaches the "a" entry's quarter second
        assert age_b < age_a - 0.1, "head age must follow the ring"


# ---------------------------------------------------------------------------
# pipeline-level ordering + chaos storms


def _build(tmp_path, name, thread_count, capacity=40):
    pqm = ProcessQueueManager()
    mgr = CollectionPipelineManager(pqm, SenderQueueManager())
    runner = ProcessorRunner(pqm, mgr, thread_count=thread_count)
    runner.init()
    out = tmp_path / f"{name}.jsonl"
    diff = ConfigDiff()
    diff.added[name] = {
        "inputs": [{"Type": "input_static_file_onetime",
                    "FilePaths": ["/nonexistent"]}],
        "global": {"ProcessQueueCapacity": capacity},
        "processors": [{"Type": "processor_parse_regex_tpu",
                        "Regex": r"(\w+):(\d+)", "Keys": ["src", "seq"]}],
        "flushers": [{"Type": "flusher_file", "FilePath": str(out),
                      "MinCnt": 1, "MinSizeBytes": 1}],
    }
    mgr.update_pipelines(diff)
    return pqm, mgr, runner, mgr.find_pipeline(name), out


def _push_all(pqm, key, sources, per_source, lines_per_group=8,
              seq_base=0):
    """Per source s: groups of lines 's<g>:<seq>' with a strictly
    increasing seq — readable back from the flushed JSON.  ``seq_base``
    lets a second wave continue each source's sequence (the mid-storm
    conservation checkpoints split one storm into waves)."""
    total = 0
    for s_i, src in enumerate(sources):
        seq = seq_base
        for _ in range(per_source):
            lines = []
            for _ in range(lines_per_group):
                lines.append(b"s%d:%d" % (s_i, seq))
                seq += 1
            g = _group(b"\n".join(lines) + b"\n", source=src)
            deadline = time.monotonic() + 30
            while not pqm.push_queue(key, g):
                assert time.monotonic() < deadline, "push starved"
                time.sleep(0.002)
            total += lines_per_group
    return total


def _read_per_source(out_path):
    per_source = {}
    for line in out_path.read_text().splitlines():
        obj = json.loads(line)
        if "src" in obj and "seq" in obj:
            per_source.setdefault(obj["src"], []).append(int(obj["seq"]))
    return per_source


class TestPerSourceOrdering:
    def test_in_order_under_four_workers(self, tmp_path):
        sources = [b"sA", b"sB", b"sC", b"sD", b"sE", b"sF"]
        pqm, mgr, runner, p, out = _build(tmp_path, "ord", 4)
        try:
            total = _push_all(pqm, p.process_queue_key, sources, 40)
            assert wait_for(lambda: pqm.all_empty(), timeout=60)
            time.sleep(0.3)
        finally:
            runner.stop()
            mgr.stop_all()
        per_source = _read_per_source(out)
        got = sum(len(v) for v in per_source.values())
        assert got == total, f"lost {total - got} events across shards"
        for src, seqs in per_source.items():
            assert seqs == sorted(seqs), (
                f"shard reordered {src}: first disorder at "
                f"{next(i for i in range(1, len(seqs)) if seqs[i] < seqs[i-1])}")
            assert len(set(seqs)) == len(seqs), f"{src} duplicated events"

    def test_same_source_same_worker(self, tmp_path):
        """The affinity invariant itself: all groups of one source are
        processed by one thread."""
        pqm = ProcessQueueManager()
        seen = {}
        lock = threading.Lock()

        class _Mgr:
            def find_pipeline_by_queue_key(self, key):
                class _P:
                    name = "aff"

                    def process_begin(self, groups):
                        # backlog-aware pops hand the worker RUNS of
                        # groups: record the worker for every group, not
                        # just the head
                        me = threading.current_thread().name
                        with lock:
                            for g in groups:
                                seen.setdefault(group_source_id(g),
                                                set()).add(me)
                        return None

                    def send(self, groups):
                        pass
                return _P()
        runner = ProcessorRunner(pqm, _Mgr(), thread_count=4)
        runner.init()
        try:
            pqm.create_or_reuse_queue(1, capacity=200)
            for i in range(120):
                assert pqm.push_queue(1, _group(b"x", b"s%d" % (i % 6)))
            assert wait_for(pqm.all_empty, timeout=30)
            time.sleep(0.2)
        finally:
            runner.stop()
        assert len(seen) == 6
        for src, threads in seen.items():
            assert len(threads) == 1, f"{src} ran on {threads}"


class TestForcedShutdownDrain:
    def test_route_processes_inline_when_inbox_closed(self):
        """A forced shutdown (stop() closed the inboxes after the drain
        join timed out) must not DROP routed groups: the dispatch loop
        processes them inline, like the old single-thread drain."""
        done = []

        class _P:
            name = "drain"

            def process_begin(self, groups):
                return None

            def send(self, groups):
                done.append(groups[0])

        class _Mgr:
            def find_pipeline_by_queue_key(self, key):
                return _P()

        pqm = ProcessQueueManager()
        runner = ProcessorRunner(pqm, _Mgr(), thread_count=2)
        runner.init()
        try:
            for ib in runner._inboxes:
                ib.close()
            runner._route((1, _group(b"x", source=b"s")))
            assert len(done) == 1, "closed-inbox route must drain inline"
        finally:
            runner.stop()


class TestMixedRoutingOrder:
    @pytest.mark.parametrize("thread_count", [1, 4])
    def test_device_then_host_groups_stay_ordered(self, thread_count):
        """The agent-drive regression: group N routes to the device (async
        lane, slow first compile), group N+1 of the same source resolves on
        the host tier and is sent inline — it must NOT overtake N."""
        import numpy as np

        from loongcollector_tpu.ops.device_plane import LatencyInjectedKernel
        plane = DevicePlane.reset_for_testing(budget_bytes=64 * 1024 * 1024)
        kernel = LatencyInjectedKernel(lambda x: x, rtt_s=0.02,
                                       serialize=False)
        sent = []
        lock = threading.Lock()

        class _P:
            name = "mixed"

            def process_begin(self, groups):
                # a run may mix "device" and "host" groups: any device
                # member keeps the whole run in flight (the runner's run =
                # one chain invocation), none ⇒ inline — same contract as
                # the real pipeline's token list
                futs = [plane.submit(kernel, (np.arange(2),), nbytes=64)
                        for g in groups
                        if int(bytes(g.get_tag(b"seq") or b"0")) % 3 == 0]
                if not futs:
                    return None     # all-host run: resolved inline
                return lambda: [f.result() for f in futs]

            def send(self, groups):
                with lock:
                    for g in groups:
                        src = bytes(g.get_tag(b"__source__") or b"")
                        sent.append((src, int(bytes(g.get_tag(b"seq")))))

        class _Mgr:
            def find_pipeline_by_queue_key(self, key):
                return _P()

        pqm = ProcessQueueManager()
        pqm.create_or_reuse_queue(1, capacity=200)
        runner = ProcessorRunner(pqm, _Mgr(), thread_count=thread_count)
        runner.init()
        try:
            for i in range(60):
                g = _group(b"x", source=b"s%d" % (i % 3))
                g.set_tag(b"seq", b"%d" % (i // 3))
                assert pqm.push_queue(1, g)
            assert wait_for(lambda: len(sent) >= 60, timeout=30)
        finally:
            runner.stop()
        per = {}
        for src, seq in sent:
            per.setdefault(src, []).append(seq)
        for src, seqs in per.items():
            assert seqs == sorted(seqs), (
                f"{src}: host-path groups overtook a laned device group: "
                f"{seqs}")


SEEDS = (3, 7, 11, 23, 42, 97, 1337, 20240803)


def _shard_storm(seed, tmp_path, tag):
    """One seeded storm through the sharded plane: queue-push rejections +
    device dispatch delays while 4 workers drain 6 sources.  The
    conservation ledger + auditor run live: the push splits into two
    waves with a quiesced residual==0 checkpoint between them (the
    acceptance criterion's mid-storm audit)."""
    DevicePlane.reset_for_testing(budget_bytes=2 * 1024 * 1024)
    ledger.enable()
    ledger.reset()
    auditor = ledger.start_auditor(interval_s=0.05)
    chaos.install(ChaosPlan(seed, {
        "bounded_queue.push": FaultSpec(
            prob=0.25, kinds=(chaos.ACTION_ERROR,), max_faults=50),
        "device_plane.submit": FaultSpec(
            prob=0.25, kinds=(chaos.ACTION_DELAY,),
            delay_range=(0.0, 0.003), max_faults=50),
    }))
    sources = [b"p%d" % i for i in range(6)]
    name = f"storm-{tag}"
    pqm, mgr, runner, p, out = _build(tmp_path, name, 4)
    try:
        total = _push_all(pqm, p.process_queue_key, sources, 6)
        # mid-storm: faults still armed, the backlog just drained — the
        # books must already balance before the second wave lands
        ledger.assert_conserved(timeout=60,
                                label=f"seed {seed} mid-storm")
        total += _push_all(pqm, p.process_queue_key, sources, 6,
                           seq_base=6 * 8)
        assert wait_for(lambda: pqm.all_empty(), timeout=60)
        time.sleep(0.3)
        ledger.assert_conserved(timeout=60,
                                label=f"seed {seed} post-storm")
        assert auditor.quiesced_audits_total > 0, (
            f"seed {seed}: the continuous auditor never saw a quiesce")
        assert auditor.residual_alarms_total == 0, (
            f"seed {seed}: the live auditor saw a conservation break")
        assert not any(
            a["alarm_type"] == AlarmType.CONSERVATION_RESIDUAL.value
            for a in AlarmManager.instance().flush()), (
            f"seed {seed}: CONSERVATION_RESIDUAL alarm raised mid-storm")
    finally:
        runner.stop()
        mgr.stop_all()
    schedule = {pt: list(evs)
                for pt, evs in chaos.schedule_by_point().items()}
    chaos.uninstall()
    per_source = _read_per_source(out)
    got = sum(len(v) for v in per_source.values())
    assert got == total, (
        f"seed {seed}: lost {total - got} events in the storm")
    for src, seqs in per_source.items():
        assert seqs == sorted(seqs), f"seed {seed}: {src} reordered"
    assert DevicePlane.instance().inflight_bytes() == 0, (
        f"seed {seed}: device budget stranded post-storm")
    return per_source, schedule


class TestShardedChaosStorm:
    @pytest.mark.parametrize("seed", SEEDS)
    def test_zero_loss_inflight_zero(self, seed, tmp_path):
        _shard_storm(seed, tmp_path, f"a{seed}")

    def test_same_seed_reproduces_schedule_and_order(self, tmp_path):
        ps1, sched1 = _shard_storm(42, tmp_path, "r1")
        ps2, sched2 = _shard_storm(42, tmp_path, "r2")
        # decision N of point P depends only on (seed, P, N); runs may draw
        # a different NUMBER of hits (push retries are timing-dependent),
        # so the shorter realized schedule must be a prefix of the longer
        for pt in set(sched1) | set(sched2):
            a, b = sched1.get(pt, []), sched2.get(pt, [])
            short, long_ = (a, b) if len(a) <= len(b) else (b, a)
            assert long_[:len(short)] == short, (
                f"point {pt}: same-seed schedules diverge")
        assert ps1 == ps2, (
            "per-source delivery order must be deterministic per shard")


class TestDeviceLaneScaling:
    def test_workers_overlap_device_rtt(self):
        """The payoff the sharded plane exists for: each worker owns one
        in-flight device lane, so N workers hide N round-trips at once.
        With a 4 ms latency-injected kernel (serialize=False — a device
        with parallel execution queues) and negligible host work, 4
        workers must drain a 40-group backlog materially faster than 1.
        On a latency-bound workload this is scheduling, not CPU, so it
        holds even on a starved 2-vCPU host."""
        import numpy as np

        from loongcollector_tpu.ops.device_plane import LatencyInjectedKernel
        kernel = LatencyInjectedKernel(lambda x: x, rtt_s=0.004,
                                       serialize=False)
        plane = DevicePlane.reset_for_testing(
            budget_bytes=64 * 1024 * 1024)
        done = []
        lock = threading.Lock()

        class _P:
            name = "dev"

            def process_begin(self, groups):
                fut = plane.submit(kernel, (np.arange(4),), nbytes=1024)
                n_grp = len(groups)

                def finish():
                    fut.result()
                    with lock:
                        done.extend([1] * n_grp)
                return finish

            def send(self, groups):
                pass

        class _Mgr:
            def find_pipeline_by_queue_key(self, key):
                return _P()

        def drain_seconds(tc, n=40):
            done.clear()
            pqm = ProcessQueueManager()
            pqm.create_or_reuse_queue(1, capacity=n + 1)
            for i in range(n):
                assert pqm.push_queue(1, _group(b"x", b"s%d" % (i % 8)))
            # run_max_groups=1: this measures PER-GROUP device round-trip
            # overlap across lanes — backlog-aware run batching would
            # collapse the 40 round trips themselves (a different win,
            # benched as the columnar hand-off)
            runner = ProcessorRunner(pqm, _Mgr(), thread_count=tc,
                                     run_max_groups=1)
            t0 = time.perf_counter()
            runner.init()
            assert wait_for(lambda: len(done) >= n, timeout=30)
            dt = time.perf_counter() - t0
            runner.stop()
            return dt

        t1 = drain_seconds(1)
        t4 = drain_seconds(4)
        assert plane.inflight_bytes() == 0
        assert t1 / t4 >= 1.4, (
            f"4 device lanes should overlap RTTs: 1 worker {t1*1e3:.0f} ms "
            f"vs 4 workers {t4*1e3:.0f} ms")


class TestTraceStructurePerShard:
    def test_deterministic_span_multiset(self, tmp_path):
        """Two same-seed storms trace the same span population (names ×
        status), even though 4 workers interleave wall-clock order."""
        def run(tag):
            tracer = trace.enable(trace.TraceConfig(sample_rate=1.0,
                                                    seed=7))
            try:
                _, schedule = _shard_storm(23, tmp_path, tag)
                # spans that are there by chance (a collection, an audit
                # pass on its wall-clock cadence) are no part of the storm
                spans = sorted((s.name, s.status)
                               for s in tracer.finished_spans()
                               if s.name not in trace.VOLATILE_SPANS)
                events = [ev.name for ev in tracer.timeline()]
            finally:
                trace.disable()
            return spans, events, schedule
        s1, e1, sched1 = run("t1")
        s2, e2, sched2 = run("t2")
        # span population is group-bound, so it replays exactly; injected
        # fault COUNTS are hit-count-dependent (push retries), so the
        # invariant there is zero silent injections per run, not equality
        assert s1 == s2
        assert set(e1) == set(e2)
        for events, sched in ((e1, sched1), (e2, sched2)):
            injected = sum(len(v) for v in sched.values())
            assert events.count("chaos.inject") == injected, (
                "every injected fault must appear on the trace timeline")
        assert any(n == "pipeline.process" for n, _ in s1)
