"""Monitor subsystem tests: metrics, alarms, self-monitor conversion,
host-monitor collectors, watchdog sampling."""

import time

import pytest

from loongcollector_tpu.input.host_monitor import (COLLECTORS,
                                                   HostMonitorInputRunner)
from loongcollector_tpu.models import EventType
from loongcollector_tpu.monitor.alarms import (AlarmLevel, AlarmManager,
                                               AlarmType)
from loongcollector_tpu.monitor.metrics import (MetricsRecord, ReadMetrics,
                                                WriteMetrics)
from loongcollector_tpu.monitor.self_monitor import SelfMonitorServer
from loongcollector_tpu.monitor.watchdog import _read_self_stat
from loongcollector_tpu.pipeline.queue.process_queue_manager import \
    ProcessQueueManager


class TestMetrics:
    def test_counter_collect_resets(self):
        rec = MetricsRecord(category="test", labels={"x": "1"})
        c = rec.counter("events")
        c.add(5)
        snap = rec.snapshot(reset_counters=True)
        assert snap["counters"]["events"] == 5
        assert rec.snapshot()["counters"]["events"] == 0

    def test_gc_deleted(self):
        rec = MetricsRecord(category="gc_test")
        n_before = len(WriteMetrics.instance().records())
        rec.mark_deleted()
        WriteMetrics.instance().gc_deleted()
        assert len(WriteMetrics.instance().records()) == n_before - 1


class TestAlarms:
    def test_aggregation(self):
        mgr = AlarmManager()
        for _ in range(5):
            mgr.send_alarm(AlarmType.SEND_FAIL, "endpoint down",
                           AlarmLevel.ERROR, pipeline="p1")
        out = mgr.flush()
        assert len(out) == 1
        assert out[0]["alarm_count"] == "5"
        assert out[0]["alarm_level"] == "error"
        assert mgr.empty()


class TestSelfMonitor:
    def test_metrics_and_alarms_to_groups(self):
        # start from a clean singleton: an earlier file on this worker may
        # have left an alarm of its own (a chaos storm's demotions)
        AlarmManager.instance().flush()
        pqm = ProcessQueueManager()
        pqm.create_or_reuse_queue(101)
        pqm.create_or_reuse_queue(102)
        server = SelfMonitorServer()
        server.process_queue_manager = pqm
        server.set_metrics_pipeline(101)
        server.set_alarms_pipeline(102)
        rec = MetricsRecord(category="pipeline", labels={"pipeline_name": "x"})
        rec.counter("in_events_total").add(7)
        AlarmManager.instance().send_alarm(AlarmType.PARSE_LOG_FAIL, "boom")
        server.send_once()
        key, mgroup = pqm.pop_item(timeout=0)
        assert key == 101
        assert mgroup.event_type() == EventType.METRIC
        key, agroup = pqm.pop_item(timeout=0)
        assert key == 102
        contents = {k.to_bytes(): v.to_bytes()
                    for k, v in agroup.events[0].contents}
        assert contents[b"alarm_type"] == b"PARSE_LOG_FAIL_ALARM"


class TestHostMonitor:
    @pytest.mark.parametrize("name", ["cpu", "mem", "disk", "net", "system",
                                      "process"])
    def test_collectors_produce_metrics(self, name):
        coll = COLLECTORS[name]()
        coll.collect()
        time.sleep(0.02)
        out = coll.collect()  # rate collectors need two samples
        if name in ("mem", "disk", "system", "process"):
            assert out, name
        for metric, value, tags in out:
            assert isinstance(metric, str) and isinstance(value, float)

    def test_runner_pushes_group(self):
        pqm = ProcessQueueManager()
        pqm.create_or_reuse_queue(7)
        runner = HostMonitorInputRunner()
        runner.process_queue_manager = pqm
        runner.collect_once([COLLECTORS["mem"]()], 7)
        key, group = pqm.pop_item(timeout=0)
        assert key == 7
        names = {str(ev.name) for ev in group.events}
        assert "memory_total_bytes" in names


class TestCircuitAlarmPropagation:
    """ISSUE 2 satellite: SINK_CIRCUIT_OPEN and watchdog-breach alarms must
    surface in self-monitor output (the agent's own data plane), not just
    in logs."""

    def _alarm_types(self, pqm, server):
        server.send_once()
        types = set()
        while True:
            popped = pqm.pop_item(timeout=0)
            if popped is None or popped[1] is None:
                break
            _, group = popped
            for ev in group.events:
                contents = {k.to_bytes(): v.to_bytes()
                            for k, v in getattr(ev, "contents", [])}
                if b"alarm_type" in contents:
                    types.add(contents[b"alarm_type"])
        return types

    def _server(self, pqm):
        server = SelfMonitorServer()
        server.process_queue_manager = pqm
        server.set_alarms_pipeline(301)
        return server

    def test_sink_circuit_open_reaches_self_monitor(self):
        from loongcollector_tpu.runner.circuit import (BreakerState,
                                                       SinkCircuitBreaker)
        AlarmManager.instance().flush()   # start from a clean singleton
        pqm = ProcessQueueManager()
        pqm.create_or_reuse_queue(301)
        server = self._server(pqm)
        br = SinkCircuitBreaker("t/flusher_x", failure_threshold=2,
                                cooldown_s=30.0, pipeline="t")
        br.on_failure()
        assert br.state is BreakerState.CLOSED
        br.on_failure()
        assert br.state is BreakerState.OPEN
        assert br.metrics.gauge("state").value == float(BreakerState.OPEN)
        types = self._alarm_types(pqm, server)
        assert b"SINK_CIRCUIT_OPEN_ALARM" in types

    def test_watchdog_breach_alarm_reaches_self_monitor(self):
        from loongcollector_tpu.monitor.watchdog import LoongCollectorMonitor
        from loongcollector_tpu.utils import flags
        AlarmManager.instance().flush()
        pqm = ProcessQueueManager()
        pqm.create_or_reuse_queue(301)
        server = self._server(pqm)
        breaches = []
        mon = LoongCollectorMonitor(interval_s=0.01,
                                    on_limit_breach=breaches.append)
        old_mem = flags.get_flag("memory_usage_limit_mb")
        flags.set_flag("memory_usage_limit_mb", 1)   # rss always over
        try:
            mon.start()
            deadline = time.monotonic() + 5
            while not breaches and time.monotonic() < deadline:
                time.sleep(0.05)
        finally:
            mon.stop()
            flags.set_flag("memory_usage_limit_mb", old_mem)
        assert breaches and "rss" in breaches[0], \
            "restart-request callback should carry the breach description"
        types = self._alarm_types(pqm, server)
        assert b"MEM_EXCEED_LIMIT_ALARM" in types


class TestWatchdog:
    def test_self_stat_readable(self):
        ticks, rss = _read_self_stat()
        assert ticks >= 0 and rss > 0


class TestHostMeta:
    def test_entities(self):
        from loongcollector_tpu.input.host_monitor import HostMetaCollector
        ents = HostMetaCollector().collect_entities()
        assert ents[0]["__entity_type__"] == "host"
        procs = [e for e in ents if e["__entity_type__"] == "process"]
        assert procs and any(e["pid"] == "1" for e in procs)

    def test_input_pushes_group(self):
        from loongcollector_tpu.input.host_monitor import (
            HostMonitorInputRunner, InputHostMeta)
        from loongcollector_tpu.pipeline.plugin.interface import PluginContext
        pqm = ProcessQueueManager()
        pqm.create_or_reuse_queue(88)
        HostMonitorInputRunner.instance().process_queue_manager = pqm
        inp = InputHostMeta()
        ctx = PluginContext("hm")
        ctx.process_queue_key = 88
        inp.init({}, ctx)
        inp.collect_once()
        key, group = pqm.pop_item(timeout=0)
        assert key == 88
        assert group.get_tag(b"__source__") == b"host_meta"


class TestProcessEntity:
    def test_entity_and_link_events(self):
        import time as _t

        from loongcollector_tpu.input.host_monitor import \
            ProcessEntityCollector
        c = ProcessEntityCollector(top_n=5, interval_s=30)
        c.collect_group()            # tick baseline
        _t.sleep(0.2)
        g = c.collect_group()
        rows = [{k.to_str(): v.to_bytes() for k, v in ev.contents}
                for ev in g.events]
        ents = [r for r in rows if "__entity_id__" in r]
        links = [r for r in rows if "__src_entity_id__" in r]
        assert len(ents) == 5 and len(links) == 5
        e = ents[0]
        assert e["__domain__"] == b"infra"
        assert e["__entity_type__"] == b"infra.host.process"
        assert e["pid"].isdigit() and e["ppid"].lstrip(b"-").isdigit()
        assert int(e["ktime"]) > 0
        assert e["__keep_alive_seconds__"] == b"60"
        # entity id is stable across collections for the same process
        g2 = c.collect_group()
        ids2 = {r2["pid"]: r2["__entity_id__"] for ev2 in g2.events
                for r2 in [{k.to_str(): v.to_bytes()
                            for k, v in ev2.contents}]
                if "__entity_id__" in r2}
        if e["pid"] in ids2:
            assert ids2[e["pid"]] == e["__entity_id__"]
        # links point at the host entity
        assert links[0]["__dest_entity_type__"] == b"acs.host.instance"
        assert links[0]["__relation_type__"] == b"update"

    def test_registered(self):
        from loongcollector_tpu.pipeline.plugin.registry import \
            PluginRegistry
        r = PluginRegistry.instance()
        r.load_static_plugins()
        assert r.create_input("input_process_entity") is not None


class TestAlarmEmissionSites:
    """Round-5: taxonomy types are wired to REAL emission sites, not just
    declared (reference AlarmManager call sites across subsystems)."""

    def _flush_types(self):
        from loongcollector_tpu.monitor.alarms import AlarmManager
        return {a["alarm_type"] for a in AlarmManager.instance().flush()}

    def test_parse_fail_emits(self):
        from loongcollector_tpu.models import PipelineEventGroup, SourceBuffer
        from loongcollector_tpu.pipeline.plugin.interface import PluginContext
        from loongcollector_tpu.processor.parse_regex import \
            ProcessorParseRegex
        from loongcollector_tpu.processor.split_log_string import \
            ProcessorSplitLogString
        self._flush_types()
        ctx = PluginContext()
        sb = SourceBuffer()
        g = PipelineEventGroup(sb)
        g.add_raw_event(1).set_content(sb.copy_string(b"no digits here\n"))
        sp = ProcessorSplitLogString(); sp.init({}, ctx); sp.process(g)
        p = ProcessorParseRegex()
        p.init({"Regex": r"(\d+)", "Keys": ["n"]}, ctx)
        p.process(g)
        assert "PARSE_LOG_FAIL_ALARM" in self._flush_types()

    def test_bad_config_emits(self, tmp_path):
        from loongcollector_tpu.config.watcher import load_config_file
        self._flush_types()
        bad = tmp_path / "broken.json"
        bad.write_text("{not json")
        assert load_config_file(str(bad)) is None
        assert "USER_CONFIG_ALARM" in self._flush_types()

    def test_timestamp_fail_emits(self):
        from loongcollector_tpu.pipeline.plugin.interface import PluginContext
        from loongcollector_tpu.processor.parse_timestamp import \
            ProcessorParseTimestamp
        self._flush_types()
        p = ProcessorParseTimestamp()
        p.init({"SourceFormat": "%Y-%m-%d"}, PluginContext())
        assert p._parse_one(b"not-a-date") == -1
        assert "PARSE_TIME_FAIL_ALARM" in self._flush_types()

    def test_send_verdict_alarms(self):
        from loongcollector_tpu.pipeline.queue.sender_queue import (
            SenderQueueItem, SenderQueueManager)
        from loongcollector_tpu.runner.flusher_runner import FlusherRunner
        self._flush_types()
        sqm = SenderQueueManager()
        sqm.create_or_reuse_queue(901)

        class _F:
            name = "f"; plugin_id = "f/0"; context = None
            sender_queue = None; queue_key = 901
            def on_send_done(self, item, status, body):
                return {500: "retry", 429: "retry_slow", 400: "drop"}[status]
            def spill_identity(self):
                return {}

        runner = FlusherRunner(sqm, http_sink=None)
        for status in (500, 429, 400):
            item = SenderQueueItem(data=b"x", raw_size=1, flusher=_F(),
                                   queue_key=901)
            q = sqm.get_queue(901)
            if q is not None:
                q.push(item)
            runner._on_done(item, status, b"")
        types = self._flush_types()
        assert "SEND_DATA_FAIL_ALARM" in types
        assert "SEND_QUOTA_EXCEED_ALARM" in types
        assert "DISCARD_DATA_ALARM" in types
