"""loongcolumn (ISSUE 11): zero-materialization columnar event path.

Four contracts under test:

1. **Lazy materialization boundary** — columnar groups flow through
   capable plugin chains with ZERO per-event objects minted; a plugin
   without ``supports_columnar`` gets counted, attributed materialization
   at ITS instance boundary; ``requires_columnar`` stages are never
   materialized even in dict mode.
2. **Golden byte-identity** — the same input through the columnar path
   and the dict path (``set_columnar_enabled(False)``) produces
   byte-identical output at every NDJSON-riding sink: file, stdout,
   kafka, clickhouse, doris, elasticsearch, loki.
3. **Backlog-aware hand-off** — byte-bounded process queues, run pops,
   inline batch-timeout flushes, and the sender wake event; the
   ``queue_wait`` p50 regression pin (BENCH_r08's 131.072 ms plateau was
   capacity × service-time residence in a count-only-bounded queue,
   reported at the log2 bucket upper bound — NOT a timer stall; the byte
   watermark keeps residence tracking load).
4. **Columnar chaos storm** — 8 seeded storms on the columnar path with
   the conservation ledger live: residual == 0 at mid-storm and
   post-storm quiesce checkpoints, zero loss, per-source order, and zero
   materialization.
"""

import io
import json
import threading
import time

import pytest

from loongcollector_tpu import chaos, models
from loongcollector_tpu.chaos import ChaosPlan, FaultSpec
from loongcollector_tpu.models import (EventGroupMetaKey, PipelineEventGroup,
                                       SourceBuffer)
from loongcollector_tpu.monitor import ledger
from loongcollector_tpu.monitor.alarms import AlarmManager, AlarmType
from loongcollector_tpu.ops.device_plane import DevicePlane
from loongcollector_tpu.pipeline.pipeline_manager import (
    CollectionPipelineManager, ConfigDiff)
from loongcollector_tpu.pipeline.plugin.instance import (FlusherInstance,
                                                         ProcessorInstance)
from loongcollector_tpu.pipeline.plugin.interface import (PluginContext,
                                                          Processor)
from loongcollector_tpu.pipeline.queue.bounded_queue import (
    BoundedProcessQueue, queue_wait_histogram)
from loongcollector_tpu.pipeline.queue.process_queue_manager import \
    ProcessQueueManager
from loongcollector_tpu.pipeline.queue.sender_queue import (SenderQueueItem,
                                                            SenderQueueManager)
from loongcollector_tpu.runner.processor_runner import ProcessorRunner

from conftest import wait_for

SEEDS = [3, 7, 11, 19, 23, 31, 43, 59]

RX = r"(\w+):(\d+)"
RX_KEYS = ["src", "seq"]


@pytest.fixture(autouse=True)
def _columnar_on():
    """Every test starts on the columnar fast path with fresh counters,
    and with no ledger or alarm an earlier file on this worker left behind
    (test_loongprof leaves the ledger on)."""
    prev = models.set_columnar_enabled(True)
    models.reset_churn_stats()
    ledger.disable()
    AlarmManager.instance().flush()
    yield
    models.set_columnar_enabled(prev)


def _group(payload: bytes, source=None, ts: int = 1700000002
           ) -> PipelineEventGroup:
    sb = SourceBuffer(len(payload) + 128)
    g = PipelineEventGroup(sb)
    g.add_raw_event(ts).set_content(sb.copy_string(payload))
    if source is not None:
        g.set_tag(b"__source__", source)
    return g


def _chain(*cfgs):
    from loongcollector_tpu.pipeline.plugin.registry import PluginRegistry
    reg = PluginRegistry.instance()
    reg.load_static_plugins()
    ctx = PluginContext("col")
    insts = []
    for i, cfg in enumerate(cfgs):
        p = reg.create_processor(cfg["Type"])
        assert p is not None and p.init(cfg, ctx)
        insts.append(ProcessorInstance(p, f"{cfg['Type']}/{i}"))
    return insts


def _split_parse_chain():
    return _chain({"Type": "processor_split_log_string_native"},
                  {"Type": "processor_parse_regex_tpu", "Regex": RX,
                   "Keys": RX_KEYS})


def _run(insts, group):
    for inst in insts:
        inst.process([group])
    return group


PAYLOAD = b"\n".join(b"s%d:%d" % (i % 4, i) for i in range(64)) + b"\n"


# ---------------------------------------------------------------------------
# 1. the lazy materialization boundary


class TestMaterializationBoundary:
    def test_capable_chain_mints_zero_objects(self):
        g = _run(_split_parse_chain(), _group(PAYLOAD))
        assert g.is_columnar() and not g._events
        churn = models.churn_stats()
        assert churn["materialized_events"] == 0, churn

    def test_non_capable_plugin_materializes_at_its_boundary(self):
        class _RowPlugin(Processor):
            name = "processor_rowly"

            def process(self, group):
                assert group._events, "boundary must have materialized"

        insts = _split_parse_chain()
        rp = _RowPlugin()
        rp.init({}, PluginContext("col"))
        insts.append(ProcessorInstance(rp, "rowly/0"))
        g = _run(insts, _group(PAYLOAD))
        assert g._events
        churn = models.churn_stats()
        assert churn["materialized_events"] == 64
        assert churn["by_boundary"] == {"rowly/0": 64}, (
            "materialization must be attributed to the plugin that "
            "forced it")

    def test_requires_columnar_stage_never_materialized(self):
        insts = _chain({"Type": "processor_split_log_string_native"},
                       {"Type": "processor_split_multiline_log_string_native",
                        "Multiline": {"StartPattern": r"s\d+:\d+"}})
        prev = models.set_columnar_enabled(False)   # dict mode
        try:
            g = _run(insts, _group(PAYLOAD))
        finally:
            models.set_columnar_enabled(prev)
        # the multiline stage ran on columns (it has no row path); the
        # dict-mode materialization waits for the next row-capable
        # boundary
        assert g.is_columnar()
        assert models.churn_stats()["materialized_events"] == 0

    def test_non_capable_flusher_materializes_at_send(self):
        class _RowSink:
            name = "flusher_rowsink"
            supports_columnar = False

            def send(self, group):
                assert group._events
                return True

        g = _run(_split_parse_chain(), _group(PAYLOAD))
        fi = FlusherInstance(_RowSink(), "rowsink/0")
        assert fi.send(g)
        assert models.churn_stats()["by_boundary"] == {"rowsink/0": 64}

    def test_capable_flusher_keeps_columns(self):
        from loongcollector_tpu.flusher.blackhole import FlusherBlackHole
        g = _run(_split_parse_chain(), _group(PAYLOAD))
        bh = FlusherBlackHole()
        bh.init({}, PluginContext("col"))
        fi = FlusherInstance(bh, "bh/0")
        assert fi.send(g)
        assert g.is_columnar() and not g._events
        assert models.churn_stats()["materialized_events"] == 0

    def test_dict_mode_materializes_everywhere(self):
        prev = models.set_columnar_enabled(False)
        try:
            g = _run(_split_parse_chain(), _group(PAYLOAD))
        finally:
            models.set_columnar_enabled(prev)
        assert g._events
        assert models.churn_stats()["materialized_events"] == 64


# ---------------------------------------------------------------------------
# 2. golden byte-identity across every NDJSON-riding sink


def _both_paths():
    """The same input through the columnar chain and the dict chain."""
    g_col = _run(_split_parse_chain(), _group(PAYLOAD, source=b"gold"))
    prev = models.set_columnar_enabled(False)
    try:
        g_dict = _run(_split_parse_chain(), _group(PAYLOAD, source=b"gold"))
        if g_dict.is_columnar() and not g_dict._events:
            g_dict.materialize("sink")
    finally:
        models.set_columnar_enabled(prev)
    assert g_col.is_columnar() and not g_col._events
    assert g_dict._events
    return g_col, g_dict


class TestGoldenSinkEquivalence:
    def test_file_sink_byte_identical(self, tmp_path):
        from loongcollector_tpu.flusher.file import FlusherFile
        outs = []
        for tag, g in zip(("col", "dict"), _both_paths()):
            f = FlusherFile()
            path = tmp_path / f"{tag}.jsonl"
            assert f.init({"FilePath": str(path), "MinCnt": 1,
                           "MinSizeBytes": 1}, PluginContext("col"))
            assert f.send(g)
            f.stop()
            outs.append(path.read_bytes())
        assert outs[0] == outs[1] and outs[0]

    def test_stdout_sink_byte_identical(self):
        from loongcollector_tpu.flusher.stdout import FlusherStdout
        outs = []
        for g in _both_paths():
            f = FlusherStdout()
            assert f.init({}, PluginContext("col"))
            f._stream = io.StringIO()
            assert f.send(g)
            f.flush_all()
            outs.append(f._stream.getvalue())
            f.batcher.close()
        assert outs[0] == outs[1] and outs[0]

    def test_kafka_sink_byte_identical(self):
        from loongcollector_tpu.flusher.kafka import FlusherKafka

        class _FakeProducer:
            def __init__(self):
                self.records = []

            def send(self, topic, records):
                self.records.extend((topic,) + r for r in records)

            def close(self):
                pass

        outs = []
        for g in _both_paths():
            f = FlusherKafka()
            assert f.init({"Brokers": ["localhost:9092"], "Topic": "t",
                           "MinCnt": 1, "MinSizeBytes": 1},
                          PluginContext("col"))
            f.producer.close()
            fake = f.producer = _FakeProducer()
            assert f.send(g)
            f.batcher.flush_all()
            assert wait_for(lambda: len(fake.records) >= 64, timeout=10)
            f.stop()
            outs.append(list(fake.records))
        assert outs[0] == outs[1] and len(outs[0]) == 64

    @pytest.mark.parametrize("sink", ["clickhouse", "doris",
                                      "elasticsearch", "loki"])
    def test_http_family_payload_byte_identical(self, sink):
        from loongcollector_tpu.flusher.clickhouse import FlusherClickHouse
        from loongcollector_tpu.flusher.doris import FlusherDoris
        from loongcollector_tpu.flusher.elasticsearch import \
            FlusherElasticsearch
        from loongcollector_tpu.flusher.loki import FlusherLoki
        mk = {
            "clickhouse": (FlusherClickHouse,
                           {"Addresses": ["http://h:8123"], "Table": "t"}),
            "doris": (FlusherDoris,
                      {"Addresses": ["http://h:8030"], "Database": "d",
                       "Table": "t"}),
            "elasticsearch": (FlusherElasticsearch,
                              {"Addresses": ["http://h:9200"],
                               "Index": "logs"}),
            "loki": (FlusherLoki, {"URL": "http://h:3100"}),
        }[sink]
        outs = []
        for g in _both_paths():
            f = mk[0]()
            assert f.init(dict(mk[1]), PluginContext("col"))
            built = f.build_payload([g])
            assert built is not None
            outs.append(bytes(built[0]))
            f.batcher.close()
        assert outs[0] == outs[1] and outs[0]

    def test_columnar_sink_paths_mint_zero_objects(self, tmp_path):
        from loongcollector_tpu.flusher.file import FlusherFile
        g = _run(_split_parse_chain(), _group(PAYLOAD, source=b"gold"))
        f = FlusherFile()
        assert f.init({"FilePath": str(tmp_path / "o.jsonl"), "MinCnt": 1,
                       "MinSizeBytes": 1}, PluginContext("col"))
        assert FlusherInstance(f, "file/0").send(g)
        f.stop()
        assert models.churn_stats()["materialized_events"] == 0


# ---------------------------------------------------------------------------
# 3. backlog-aware hand-off


class TestByteWatermark:
    def test_push_blocks_on_bytes_not_just_count(self):
        q = BoundedProcessQueue(1, capacity=1000, max_bytes=64 * 1024)
        n = 0
        while q.push(_group(b"x" * 8192)):
            n += 1
            assert n < 100, "byte watermark never engaged"
        # 64 KiB / ~8 KiB groups ⇒ high watermark around 8 groups
        assert 6 <= n <= 12
        assert not q.is_valid_to_push()
        # drain below the low watermark ⇒ valid again
        while q.bytes_queued() > 64 * 1024 * 2 / 3:
            assert q.pop() is not None
        assert q.is_valid_to_push()

    def test_zero_disables_byte_bound(self):
        q = BoundedProcessQueue(1, capacity=5, max_bytes=0)
        for _ in range(4):
            assert q.push(_group(b"x" * 100000))
        assert q.is_valid_to_push()

    def test_bytes_accounting_balances(self):
        q = BoundedProcessQueue(1, capacity=100, max_bytes=10**9)
        for _ in range(10):
            q.push(_group(b"y" * 1000))
        assert q.bytes_queued() > 0
        while q.pop() is not None:
            pass
        assert q.bytes_queued() == 0


class TestPopRuns:
    def test_pop_run_drains_backlog_in_order(self):
        q = BoundedProcessQueue(1, capacity=100)
        for i in range(10):
            q.push(_group(b"g%d" % i))
        run = q.pop_run(max_groups=8, max_bytes=1 << 30)
        assert len(run) == 8
        rest = q.pop_run(max_groups=8, max_bytes=1 << 30)
        assert len(rest) == 2
        texts = [bytes(g.events[0].content.to_bytes()) for g in run + rest]
        assert texts == [b"g%d" % i for i in range(10)]

    def test_pop_run_respects_byte_cap(self):
        q = BoundedProcessQueue(1, capacity=100)
        for i in range(10):
            q.push(_group(b"z" * 1000))
        run = q.pop_run(max_groups=10, max_bytes=3500)
        # first group always pops; byte cap stops the run after ~3
        assert 3 <= len(run) <= 4

    def test_manager_run_single_key(self):
        pqm = ProcessQueueManager()
        pqm.create_or_reuse_queue(1, capacity=100)
        pqm.create_or_reuse_queue(2, capacity=100)
        for i in range(6):
            pqm.push_queue(1, _group(b"a"))
            pqm.push_queue(2, _group(b"b"))
        key, groups = pqm.pop_run(timeout=0)
        assert len(groups) == 6
        assert all(
            bytes(g.events[0].content.to_bytes()) ==
            (b"a" if key == 1 else b"b") for g in groups)

    def test_inbox_get_run_groups_same_key_prefix(self):
        from loongcollector_tpu.runner.processor_runner import _ShardInbox
        ib = _ShardInbox(capacity=8)
        for i in range(3):
            assert ib.put((1, f"a{i}"))
        assert ib.put((2, "b0"))
        key, groups = ib.get_run(timeout=0)
        assert key == 1 and groups == ["a0", "a1", "a2"]
        key, groups = ib.get_run(timeout=0)
        assert key == 2 and groups == ["b0"]


class TestBatcherInlineTimeFlush:
    def test_overdue_batch_flushes_on_next_add_not_the_pump(self):
        from loongcollector_tpu.pipeline.batch.batcher import Batcher
        from loongcollector_tpu.pipeline.batch.flush_strategy import \
            FlushStrategy
        flushed = []
        b = Batcher(FlushStrategy(min_cnt=10**6, min_size_bytes=10**9,
                                  timeout_secs=0.05),
                    on_flush=lambda groups: flushed.append(groups))
        try:
            b.add(_group(b"one"))
            assert not flushed
            time.sleep(0.08)
            # no central pump runs here: the add itself finds the batch due
            b.add(_group(b"two"))
            assert flushed and sum(len(g) for g in flushed[0]) == 2
        finally:
            b.close()


class TestSenderWake:
    def test_push_wakes_waiter_immediately(self):
        sqm = SenderQueueManager()
        q = sqm.create_or_reuse_queue(9, capacity=4)
        woke = []

        def waiter():
            t0 = time.perf_counter()
            sqm.wait_for_data(2.0)
            woke.append(time.perf_counter() - t0)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        q.push(SenderQueueItem(b"x", 1, queue_key=9))
        t.join(timeout=5)
        assert woke and woke[0] < 1.0, (
            "sender push must wake the runner, not wait out the timeout")


class TestQueueWaitUnderLoad:
    def test_queue_wait_p50_tracks_load_not_capacity(self, tmp_path):
        """Regression pin for the BENCH_r08 artifact: queue_wait p50 ==
        131.072 ms (p50 == p90, exactly the log2 bucket upper bound that
        contains capacity x service-time for 40 x ~500 KB chunks).  Root
        cause: residence in a count-only-bounded queue — each group
        waited out the whole standing backlog regardless of load.  With
        the byte watermark the standing backlog is bounded in bytes, so
        p50 under sustained load must sit well under both the old
        plateau and the batch flush interval."""
        from loongcollector_tpu.runner.processor_runner import \
            BATCH_FLUSH_INTERVAL_S
        pqm = ProcessQueueManager()
        mgr = CollectionPipelineManager(pqm, SenderQueueManager())
        runner = ProcessorRunner(pqm, mgr, thread_count=1)
        runner.init()
        try:
            diff = ConfigDiff()
            diff.added["qw"] = {
                "inputs": [{"Type": "input_static_file_onetime",
                            "FilePaths": ["/nonexistent"]}],
                "global": {"ProcessQueueCapacity": 40},
                "processors": [{"Type": "processor_parse_regex_tpu",
                                "Regex": RX, "Keys": RX_KEYS}],
                "flushers": [{"Type": "flusher_blackhole"}],
            }
            mgr.update_pipelines(diff)
            p = mgr.find_pipeline("qw")
            bh = p.flushers[0].plugin
            # ~500 KB chunks, the tailing reader's shape: under the old
            # count-only bound 40 of these stand in the queue
            chunk = b"\n".join(b"s%d:%d" % (i % 8, i)
                               for i in range(40000)) + b"\n"
            # warm-up then reset the shared histogram
            assert pqm.push_queue(p.process_queue_key, _group(chunk))
            assert wait_for(lambda: bh.total_events > 0, timeout=60)
            queue_wait_histogram().snapshot(reset=True)
            pushed = 0
            deadline = time.monotonic() + 60
            while pushed < 40 and time.monotonic() < deadline:
                if pqm.push_queue(p.process_queue_key, _group(chunk)):
                    pushed += 1
                else:
                    time.sleep(0.001)
            assert pushed == 40
            assert wait_for(pqm.all_empty, timeout=60)
            time.sleep(0.2)
        finally:
            runner.stop()
            mgr.stop_all()
        snap = queue_wait_histogram().snapshot()
        assert snap["count"] >= 40
        assert snap["p50"] < BATCH_FLUSH_INTERVAL_S, snap
        # the real pin: p50 tracks service rate (a handful of groups in
        # the byte-bounded backlog), far below the old 131 ms plateau
        assert snap["p50"] <= 0.033, (
            f"queue_wait p50 {snap['p50']*1e3:.1f} ms — the standing "
            f"backlog is count-bound again? {snap}")


# ---------------------------------------------------------------------------
# 4. columnar chaos storm with the live conservation ledger


def _storm(seed, tmp_path, tag):
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
    name = f"col-storm-{tag}"
    out = tmp_path / f"{name}.jsonl"
    pqm = ProcessQueueManager()
    mgr = CollectionPipelineManager(pqm, SenderQueueManager())
    runner = ProcessorRunner(pqm, mgr, thread_count=4)
    runner.init()
    sources = [b"p%d" % i for i in range(6)]
    try:
        diff = ConfigDiff()
        diff.added[name] = {
            "inputs": [{"Type": "input_static_file_onetime",
                        "FilePaths": ["/nonexistent"]}],
            "global": {"ProcessQueueCapacity": 40},
            "processors": [{"Type": "processor_parse_regex_tpu",
                            "Regex": RX, "Keys": RX_KEYS}],
            "flushers": [{"Type": "flusher_file", "FilePath": str(out),
                          "MinCnt": 1, "MinSizeBytes": 1}],
        }
        mgr.update_pipelines(diff)
        p = mgr.find_pipeline(name)

        def push_wave(per_source, seq_base=0):
            total = 0
            for s_i, src in enumerate(sources):
                seq = seq_base
                for _ in range(per_source):
                    lines = [b"s%d:%d" % (s_i, seq + j) for j in range(8)]
                    seq += 8
                    g = _group(b"\n".join(lines) + b"\n", source=src)
                    deadline = time.monotonic() + 30
                    while not pqm.push_queue(p.process_queue_key, g):
                        assert time.monotonic() < deadline, "push starved"
                        time.sleep(0.002)
                    total += 8
            return total

        total = push_wave(6)
        # mid-storm checkpoint: faults still armed, books must balance
        ledger.assert_conserved(timeout=60, label=f"seed {seed} mid-storm")
        total += push_wave(6, seq_base=48)
        assert wait_for(pqm.all_empty, timeout=60)
        time.sleep(0.3)
        ledger.assert_conserved(timeout=60, label=f"seed {seed} post-storm")
        assert auditor.quiesced_audits_total > 0
        assert auditor.residual_alarms_total == 0
        assert not any(
            a["alarm_type"] == AlarmType.CONSERVATION_RESIDUAL.value
            for a in AlarmManager.instance().flush())
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
    # the whole storm rode the columnar plane: not one event object
    churn = models.churn_stats()
    assert churn["materialized_events"] == 0, churn


class TestColumnarChaosStorm:
    @pytest.mark.parametrize("seed", SEEDS)
    def test_zero_loss_zero_materialization(self, seed, tmp_path):
        _storm(seed, tmp_path, f"s{seed}")


# ---------------------------------------------------------------------------
# 5. reader-side columnar group assembly


class TestReaderPresplit:
    def test_presplit_matches_split_processor(self, tmp_path):
        """A presplit reader's columns must equal what the bare reader +
        inner split processor produce — same spans, same timestamps
        source, zero per-event objects."""
        import numpy as np

        from loongcollector_tpu.input.file.reader import LogFileReader
        data = b"alpha\nbeta\n\ngamma delta\n"
        p = tmp_path / "r.log"
        p.write_bytes(data)

        r1 = LogFileReader(str(p), presplit_lines=True)
        g1 = r1.read()
        assert g1 is not None and g1.is_columnar() and not g1._events

        r2 = LogFileReader(str(p))          # bare contract: one RawEvent
        g2 = r2.read()
        assert g2 is not None and not g2.is_columnar()
        insts = _chain({"Type": "processor_split_log_string_native"})
        insts[0].process([g2])
        assert g2.is_columnar()

        c1, c2 = g1.columns, g2.columns
        assert np.array_equal(c1.offsets, c2.offsets)
        assert np.array_equal(c1.lengths, c2.lengths)
        raw1, raw2 = g1.source_buffer.raw, g2.source_buffer.raw
        lines1 = [bytes(raw1[int(o):int(o) + int(ln)])
                  for o, ln in zip(c1.offsets, c1.lengths)]
        assert lines1 == [b"alpha", b"beta", b"", b"gamma delta"]
        assert models.churn_stats()["materialized_events"] == 0

    def test_presplit_group_flows_through_pipeline(self, tmp_path):
        """Reader-assembled columns ride the whole chain: split no-ops,
        parse installs fields, sink serializes — zero materialization."""
        from loongcollector_tpu.input.file.reader import LogFileReader
        from loongcollector_tpu.pipeline.serializer.json_serializer import \
            JsonSerializer
        p = tmp_path / "p.log"
        p.write_bytes(b"s0:1\ns1:2\ns0:3\n")
        g = LogFileReader(str(p), presplit_lines=True).read()
        for inst in _split_parse_chain():
            inst.process([g])
        out = JsonSerializer().serialize([g])
        assert b'"src": "s0"' in out and b'"seq": "3"' in out
        assert g.is_columnar() and not g._events
        assert models.churn_stats()["materialized_events"] == 0

    def test_presplit_respects_dict_mode(self, tmp_path):
        """Review regression: in dict mode the reader must ship the
        RawEvent chunk — a presplit group would be materialized at the
        split boundary and silently no-op the requires_columnar multiline
        stage.  Multiline output must be identical on both paths."""
        from loongcollector_tpu.input.file.reader import LogFileReader
        from loongcollector_tpu.pipeline.serializer.json_serializer import \
            JsonSerializer
        data = (b"2024-01-02 03:04:05 ERROR boom\n"
                b"  at Foo(Foo.java:1)\n"
                b"2024-01-02 03:04:06 ERROR pow\n"
                b"  at Bar(Bar.java:2)\n"
                b"2024-01-02 03:04:07 INFO done\n")
        p = tmp_path / "ml.log"
        p.write_bytes(data)
        cfgs = ({"Type": "processor_split_log_string_native"},
                {"Type": "processor_split_multiline_log_string_native",
                 "Multiline": {"StartPattern": r"\d{4}-\d{2}-\d{2} .*"}})
        outs = []
        for columnar in (True, False):
            prev = models.set_columnar_enabled(columnar)
            try:
                g = LogFileReader(str(p), presplit_lines=True).read()
                assert g.is_columnar() == columnar
                for inst in _chain(*cfgs):
                    inst.process([g])
                if not columnar and g.is_columnar() and not g._events:
                    g.materialize("sink")
                outs.append(JsonSerializer().serialize([g]))
            finally:
                models.set_columnar_enabled(prev)
        assert outs[0] == outs[1]
        assert outs[0].count(b"ERROR boom") == 1
        assert b"at Foo" in outs[0]          # merged into the record
        assert outs[0].count(b'"__time__"') == 3   # 3 merged records


class TestCircularByteEviction:
    def test_circular_queue_evicts_on_bytes(self):
        from loongcollector_tpu.pipeline.queue.bounded_queue import \
            CircularProcessQueue
        q = CircularProcessQueue(1, capacity=1000, max_bytes=32 * 1024)
        for _ in range(20):
            assert q.push(_group(b"x" * 8192))
        # ~4 groups fit the 32 KiB bound; the rest were evicted oldest-first
        assert q.size() <= 5
        assert q.bytes_queued() <= 32 * 1024 + 8300
        assert q.total_dropped >= 15

    def test_one_oversized_group_still_ships(self):
        from loongcollector_tpu.pipeline.queue.bounded_queue import \
            CircularProcessQueue
        q = CircularProcessQueue(1, capacity=10, max_bytes=1024)
        assert q.push(_group(b"y" * 100000))
        assert q.size() == 1            # never self-evicts to empty


class TestBlackholeDigestConcurrency:
    def test_concurrent_sends_lose_no_folds(self):
        from loongcollector_tpu.flusher.blackhole import FlusherBlackHole
        bh = FlusherBlackHole()
        bh.init({"Digest": True}, PluginContext("col"))
        groups = [_run(_split_parse_chain(), _group(PAYLOAD, source=b"d%d" % i))
                  for i in range(8)]

        def pump(g):
            for _ in range(50):
                bh.send(g)

        ts = [threading.Thread(target=pump, args=(g,)) for g in groups]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        d = bh.output_digest()
        assert d["groups"] == 400
        assert d["events"] == 400 * 64
