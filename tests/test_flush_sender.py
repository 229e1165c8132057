"""The file sink's sender thread (flusher/flush_sender.py, flusher/file.py).

flusher_file's flush — serialize, write, terminal accounting — runs on a
sender thread of the flusher's own, behind a FIFO of FIFO_BATCHES batches;
the batcher's flush on the worker (or the timeout thread) only hands the
batch over.  Held here: hand-over order is file order, `flush_all()` and
`stop()` are barriers, a full FIFO blocks and drops nothing, a failed write
is one terminal drop and the sender lives on, the events of a batch that
waits or is mid-write are occupancy for the ledger's auditor, a group is
acknowledged only after its write, and flusher_stdout still writes inline.
CPU only, no device."""

import io
import json
import sys
import threading
import time

import pytest

from loongcollector_tpu import trace
from loongcollector_tpu.flusher.file import FlusherFile
from loongcollector_tpu.flusher.flush_sender import FIFO_BATCHES, FlushSender
from loongcollector_tpu.flusher.stdout import FlusherStdout
from loongcollector_tpu.models import PipelineEventGroup
from loongcollector_tpu.monitor import exposition, ledger
from loongcollector_tpu.monitor.alarms import AlarmManager
from loongcollector_tpu.monitor.ledger import ConservationAuditor
from loongcollector_tpu.pipeline.pipeline_manager import (
    CollectionPipelineManager, ConfigDiff)
from loongcollector_tpu.pipeline.plugin import interface
from loongcollector_tpu.pipeline.plugin.instance import FlusherInstance
from loongcollector_tpu.pipeline.plugin.interface import PluginContext
from loongcollector_tpu.pipeline.queue.process_queue_manager import \
    ProcessQueueManager
from loongcollector_tpu.pipeline.queue.sender_queue import SenderQueueManager
from loongcollector_tpu.pipeline.serializer.json_serializer import \
    JsonSerializer
from loongcollector_tpu.processor.parse_regex import ProcessorParseRegex
from loongcollector_tpu.processor.split_log_string import \
    ProcessorSplitLogString
from loongcollector_tpu.runner.processor_runner import ProcessorRunner

from conftest import wait_for

WAIT = 20.0     # every wait in this file is bounded


@pytest.fixture(autouse=True)
def _ledger_clean():
    ledger.disable()
    AlarmManager.instance().flush()
    yield
    ledger.disable()
    AlarmManager.instance().flush()


def _group(seq: int, n: int = 1) -> PipelineEventGroup:
    g = PipelineEventGroup()
    for i in range(n):
        ev = g.add_log_event(1)
        ev.set_content(g.source_buffer.copy_string(b"seq"),
                       g.source_buffer.copy_string(b"%d" % (seq + i)))
    return g


def _flusher(tmp_path, **config):
    f = FlusherFile()
    config.setdefault("MinSizeBytes", 1)       # every group a batch
    assert f.init({"FilePath": str(tmp_path / "sink.jsonl"), **config},
                  PluginContext(pipeline_name="p"))
    return f, tmp_path / "sink.jsonl"


def _seqs(path):
    if not path.exists():
        return []
    return [int(json.loads(line)["seq"])
            for line in path.read_text().splitlines()]


class _Gate:
    """A serializer that stalls the sender inside its flush until told."""

    def __init__(self, flusher):
        self.inner = flusher.serializer
        self.entered = threading.Event()
        self.release = threading.Event()
        flusher.serializer = self

    def _stall(self):
        self.entered.set()
        assert self.release.wait(WAIT), "the test never released the gate"

    def append_group(self, group, path):       # a batch of one group
        self._stall()
        return self.inner.append_group(group, path)

    def serialize_view(self, groups):          # any other batch
        self._stall()
        return self.inner.serialize_view(groups)


def _in_thread(fn, *args):
    th = threading.Thread(target=fn, args=args, daemon=True)
    th.start()
    return th


def _joined(th, timeout=WAIT):
    th.join(timeout)
    return not th.is_alive()


# ---------------------------------------------------------------------------
# the sender alone

class TestFlushSender:
    def test_batches_flush_one_at_a_time_in_put_order(self):
        seen, active = [], []

        def flush(groups):
            active.append(1)
            assert len(active) == 1, "two flushes at once"
            seen.append(groups)
            active.pop()

        s = FlushSender(flush, "t")
        for i in range(50):
            s.put([i], 1)
        s.drain()
        assert seen == [[i] for i in range(50)]   # never joined, never split
        assert s.inflight_events() == 0
        st = s.status()
        assert st["batches_total"] == st["offloaded_total"] == 50
        assert st["depth"] == 0 and 1 <= st["depth_max"] <= FIFO_BATCHES
        s.stop()

    def test_flush_runs_on_the_sender_thread_not_the_callers(self):
        names = []
        s = FlushSender(lambda g: names.append(
            threading.current_thread().name), "flusher_x")
        s.put([1], 1)
        s.drain()
        assert names == ["flusher_x-sender"]
        s.stop()

    def test_no_thread_until_the_first_batch(self):
        before = threading.active_count()
        s = FlushSender(lambda g: None, "t")
        s.drain()                                  # nothing handed over
        s.stop()                                   # nothing to end
        assert threading.active_count() == before
        assert s.status()["batches_total"] == 0

    def test_stop_ends_the_thread_and_a_later_put_starts_another(self):
        seen = []
        s = FlushSender(seen.append, "t")
        s.put([1], 1)
        first = s._thread
        s.stop()
        assert seen == [[1]] and not first.is_alive() and s._thread is None
        s.put([2], 1)
        s.stop()
        assert seen == [[1], [2]]

    def test_a_flush_that_raises_leaves_the_sender_alive(self):
        seen = []

        def flush(groups):
            if groups == ["bad"]:
                raise RuntimeError("flush_fn broke its word")
            seen.append(groups)

        s = FlushSender(flush, "t")
        s.put(["bad"], 3)
        s.put(["good"], 2)
        s.drain()
        assert seen == [["good"]]
        assert s.inflight_events() == 0 and s.status()["depth"] == 0
        s.stop()

    def test_full_fifo_blocks_the_caller_and_drops_nothing(self):
        entered, release, seen = threading.Event(), threading.Event(), []

        def flush(groups):
            entered.set()
            assert release.wait(WAIT)
            seen.append(groups)

        s = FlushSender(flush, "t")
        for i in range(FIFO_BATCHES):      # the one mid-flush holds a place
            s.put([i], 2)
        assert entered.wait(WAIT)
        st = s.status()
        assert st["depth"] == st["depth_max"] == FIFO_BATCHES
        assert st["enqueue_blocked_total"] == 0
        assert s.inflight_events() == 2 * FIFO_BATCHES
        th = _in_thread(s.put, [FIFO_BATCHES], 2)
        assert wait_for(lambda: s.status()["enqueue_blocked_total"] == 1,
                        timeout=WAIT)
        time.sleep(0.05)
        assert th.is_alive(), "a full FIFO must block the caller"
        assert s.status()["depth"] == FIFO_BATCHES    # no growth, no spill
        release.set()
        assert _joined(th)
        s.drain()
        assert seen == [[i] for i in range(FIFO_BATCHES + 1)]
        st = s.status()
        assert st["enqueue_blocked_total"] == 1
        assert st["enqueue_blocked_seconds"] >= 0.05
        assert st["depth_max"] == FIFO_BATCHES
        s.stop()

    @pytest.mark.parametrize("barrier", ["drain", "stop"])
    def test_barrier_waits_for_queued_and_mid_flush_batches(self, barrier):
        entered, release, seen = threading.Event(), threading.Event(), []

        def flush(groups):
            entered.set()
            assert release.wait(WAIT)
            seen.append(groups)

        s = FlushSender(flush, "t")
        s.put([0], 1)
        s.put([1], 1)
        assert entered.wait(WAIT)
        th = _in_thread(getattr(s, barrier))
        time.sleep(0.05)
        assert th.is_alive() and seen == []
        release.set()
        assert _joined(th)
        assert seen == [[0], [1]]
        s.stop()

    def test_many_producers_lose_and_reorder_nothing(self):
        """More producers than cores at a short switch interval: every
        batch is flushed exactly once and each producer's batches in the
        order it handed them over."""
        seen = []
        s = FlushSender(seen.extend, "t")
        producers, each = 16, 200

        def produce(p):
            for i in range(each):
                s.put([(p, i)], 1)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [_in_thread(produce, p) for p in range(producers)]
            assert all(_joined(th, 60) for th in threads)
            s.drain()
        finally:
            sys.setswitchinterval(old)
        assert len(seen) == producers * each == len(set(seen))
        for p in range(producers):
            assert [i for q, i in seen if q == p] == list(range(each))
        st = s.status()
        assert st["offloaded_total"] == st["batches_total"] == len(seen)
        assert st["depth_max"] <= FIFO_BATCHES and s.inflight_events() == 0
        s.stop()


# ---------------------------------------------------------------------------
# flusher_file on its sender

class TestFileSinkOnItsSender:
    def test_send_hands_over_and_flush_all_is_the_barrier(self, tmp_path):
        f, out = _flusher(tmp_path)
        gate = _Gate(f)
        try:
            assert f.send(_group(0)) and f.send(_group(1))   # returns at once
            assert gate.entered.wait(WAIT)
            assert _seqs(out) == []
            th = _in_thread(f.flush_all)
            time.sleep(0.05)
            assert th.is_alive(), "flush_all returned before the write"
            gate.release.set()
            assert _joined(th)
            assert _seqs(out) == [0, 1]      # after flush_all the file holds it
        finally:
            gate.release.set()
            f.stop()

    def test_stop_is_a_barrier_and_ends_the_thread(self, tmp_path):
        f, out = _flusher(tmp_path, MinSizeBytes=1 << 30, TimeoutSecs=3600)
        for i in range(5):
            assert f.send(_group(i))         # staged in the batcher
        assert _seqs(out) == [] and f._sender._thread is None
        assert f.stop()
        assert _seqs(out) == [0, 1, 2, 3, 4]
        assert f._sender._thread is None
        assert not [t for t in threading.enumerate()
                    if t.name == "flusher_file-sender"]

    def test_one_batch_is_one_write(self, tmp_path, monkeypatch):
        # the sender never joins queued batches into one write
        f, out = _flusher(tmp_path)
        gate = _Gate(f)
        sizes = []
        real = f._write
        monkeypatch.setattr(
            f, "_write", lambda data: (sizes.append(len(data)), real(data)))
        for i in range(4):                   # queue up behind the gate
            assert f.send(_group(10 * i, n=i + 1))
        gate.release.set()
        f.flush_all()
        lines = out.read_bytes().splitlines(keepends=True)
        assert len(sizes) == 4
        assert [len(b"".join(lines[a:b])) for a, b in
                ((0, 1), (1, 3), (3, 6), (6, 10))] == sizes
        f.stop()

    def test_timeout_flush_racing_size_flush_keeps_hand_over_order(
            self, tmp_path):
        """Two threads flush one batcher: the worker's size trigger and the
        timeout thread.  Whichever hands its batch over first is first in
        the file, every line is there once, and nothing interleaves."""
        f, out = _flusher(tmp_path, MinSizeBytes=400, TimeoutSecs=0.001)
        handed, order_lock, real_put = [], threading.Lock(), f._sender.put

        def put(groups, n_events):
            with order_lock:                 # the order of the appends
                handed.append([int(ev.get_content(b"seq").to_bytes())
                               for g in groups for ev in g.events])
                real_put(groups, n_events)
        f._sender.put = put
        done = threading.Event()

        def timeouts():
            while not done.is_set():
                f.batcher.flush_timeout()
        th = _in_thread(timeouts)
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for i in range(600):
                assert f.send(_group(i))
        finally:
            sys.setswitchinterval(old)
            done.set()
        assert _joined(th)
        f.flush_all()
        got = _seqs(out)
        assert got == [s for batch in handed for s in batch]
        assert sorted(got) == list(range(600))
        assert len(handed) > 1
        f.stop()

    def test_groups_are_acknowledged_only_after_their_write(
            self, tmp_path, monkeypatch):
        f, out = _flusher(tmp_path)
        acked = []
        monkeypatch.setattr(
            interface.ack_watermark, "ack_groups",
            lambda groups, force=False: acked.append(
                (list(groups), _seqs(out))))
        gate = _Gate(f)
        g = _group(7)
        assert f.send(g)
        assert gate.entered.wait(WAIT)
        time.sleep(0.05)
        assert acked == [], "acknowledged while its write was pending"
        gate.release.set()
        f.flush_all()
        assert acked == [([g], [7])]         # the line was in the file by then
        f.stop()

    def test_failed_write_is_one_drop_acks_and_the_sender_lives_on(
            self, tmp_path, monkeypatch):
        led = ledger.enable()
        f, out = _flusher(tmp_path)
        acked = []
        monkeypatch.setattr(interface.ack_watermark, "ack_groups",
                            lambda groups, force=False: acked.extend(groups))
        bad, good = _group(1, n=3), _group(5, n=2)
        f.file_path = str(tmp_path)          # open() of a directory raises
        assert f.send(bad)                   # the worker sees no exception
        f.flush_all()
        row = led.snapshot()["p"]
        assert row[ledger.B_DROP]["events"] == 3
        assert list(row[ledger.B_DROP]["tags"]) == ["flush_write_failed"]
        assert ledger.B_SEND_OK not in row
        assert acked == [bad]                # terminal: the checkpoint moves on
        assert f.inflight_events() == 0
        f.file_path = str(out)
        assert f.send(good)
        f.flush_all()
        assert _seqs(out) == [5, 6]
        row = led.snapshot()["p"]
        assert row[ledger.B_SEND_OK]["events"] == 2
        assert row[ledger.B_DROP]["events"] == 3     # still the one drop
        assert acked == [bad, good]
        f.stop()

    def test_inflight_events_cover_queued_and_mid_write_batches(
            self, tmp_path):
        f, out = _flusher(tmp_path)
        gate = _Gate(f)
        assert f.send(_group(0, n=3))
        assert gate.entered.wait(WAIT)       # mid-flush
        assert f.send(_group(10, n=4))       # queued behind it
        assert f.batcher.pending_events() == 0
        assert f.inflight_events() == 7
        gate.release.set()
        f.flush_all()
        assert f.inflight_events() == 0 and len(_seqs(out)) == 7
        f.stop()

    def test_full_fifo_blocks_send_and_counts_it(self, tmp_path):
        f, out = _flusher(tmp_path)
        gate = _Gate(f)
        for i in range(FIFO_BATCHES):
            assert f.send(_group(i))
        assert gate.entered.wait(WAIT)
        th = _in_thread(f.send, _group(FIFO_BATCHES))
        assert wait_for(
            lambda: f.flush_status()["enqueue_blocked_total"] == 1,
            timeout=WAIT)
        assert th.is_alive()
        # blocked inside the batcher's flush: still the batcher's events
        assert f.batcher.pending_events() == 1
        gate.release.set()
        assert _joined(th)
        f.flush_all()
        assert _seqs(out) == list(range(FIFO_BATCHES + 1))
        st = f.flush_status()
        assert st["batches_total"] == st["offloaded_total"] \
            == FIFO_BATCHES + 1
        assert st["depth_max"] == FIFO_BATCHES and st["depth"] == 0
        f.stop()

    def test_enqueue_span_holds_the_backpressure(self, tmp_path):
        f, out = _flusher(tmp_path)
        inst = FlusherInstance(f, "flusher_file/0")
        gate = _Gate(f)
        t = trace.enable()
        try:
            for i in range(FIFO_BATCHES):
                assert inst.send(_group(i))
            assert gate.entered.wait(WAIT)
            th = _in_thread(inst.send, _group(99))
            assert wait_for(
                lambda: f.flush_status()["enqueue_blocked_total"] == 1,
                timeout=WAIT)
            time.sleep(0.05)
            gate.release.set()
            assert _joined(th)
            f.flush_all()
        finally:
            gate.release.set()
            f.stop()
            spans = t.finished_spans()
            trace.disable()
        sends = {s.span_id: s for s in spans if s.name == "flusher.send"}
        enq = [s for s in spans if s.name == "flusher.enqueue"]
        assert len(enq) == len(sends) == FIFO_BATCHES + 1
        assert all(s.parent_id in sends for s in enq)
        blocked = max(enq, key=lambda s: s.duration_s)
        assert blocked.duration_s >= 0.05
        assert sends[blocked.parent_id].duration_s >= blocked.duration_s
        # the flush itself is under no span of the caller's
        for name in ("flusher.serialize", "flusher.write"):
            flushes = [s for s in spans if s.name == name]
            assert len(flushes) == FIFO_BATCHES + 1
            assert all(s.parent_id is None for s in flushes)


# ---------------------------------------------------------------------------
# a batch of one columnar group: assembled and appended in one native call

def _columnar_group(lines):
    """chunk → split → regex parse: the shape the pipeline hands the sink."""
    data = b"\n".join(lines) + b"\n"
    g = PipelineEventGroup()
    g.add_raw_event(7).set_content(g.source_buffer.copy_string(data))
    ctx = PluginContext("p")
    sp = ProcessorSplitLogString()
    sp.init({}, ctx)
    sp.process(g)
    pr = ProcessorParseRegex()
    pr.init({"Regex": r"(\w+)-(\d+) (.*)", "Keys": ["word", "seq", "rest"]},
            ctx)
    pr.process(g)
    return g


ASCII = [b"alpha-%d /index.html?q=\"x\"\\y" % i for i in range(40)]
LATIN = [b"beta-%d caf\xc3\xa9 \xff" % i for i in range(40)]


class TestOneGroupBatchIsOneNativeCall:
    def _spied(self, tmp_path, monkeypatch):
        f, out = _flusher(tmp_path)
        calls = {"append_group": [], "write": 0}
        real_append, real_write = f.serializer.append_group, f._write

        def append_group(group, path):
            done = real_append(group, path)
            calls["append_group"].append(done)
            return done

        def write(data):
            calls["write"] += 1
            real_write(data)
        monkeypatch.setattr(f.serializer, "append_group", append_group)
        monkeypatch.setattr(f, "_write", write)
        return f, out, calls

    def test_the_file_holds_what_serialize_gives(self, tmp_path,
                                                 monkeypatch):
        led = ledger.enable()
        f, out, calls = self._spied(tmp_path, monkeypatch)
        want = b""
        for _ in range(3):                  # the buffer is kept and reused
            assert f.send(_columnar_group(ASCII))
            want += JsonSerializer().serialize([_columnar_group(ASCII)])
        f.flush_all()
        assert out.read_bytes() == want and want.count(b"\n") == 120
        assert calls["write"] == 0          # no second call for the write
        assert [d[0] for d in calls["append_group"]] == [len(want) // 3] * 3
        assert led.snapshot()["p"][ledger.B_SEND_OK]["events"] == 120
        f.stop()

    def test_an_arena_with_a_high_byte_takes_the_general_path(
            self, tmp_path, monkeypatch):
        f, out, calls = self._spied(tmp_path, monkeypatch)
        assert f.send(_columnar_group(LATIN))
        f.flush_all()
        assert calls["append_group"] == [None] and calls["write"] == 1
        assert out.read_bytes() == JsonSerializer().serialize(
            [_columnar_group(LATIN)])
        assert "caf\u00e9 \ufffd" in out.read_text()     # CPython's codec
        f.stop()

    def test_a_batch_of_several_groups_is_still_one_write(
            self, tmp_path, monkeypatch):
        f, out = _flusher(tmp_path, MinSizeBytes=1 << 30, TimeoutSecs=3600)
        calls = {"append_group": 0, "write": 0}
        real_write = f._write
        monkeypatch.setattr(f.serializer, "append_group", lambda g, p: (
            calls.__setitem__("append_group", calls["append_group"] + 1)))
        monkeypatch.setattr(f, "_write", lambda data: (
            calls.__setitem__("write", calls["write"] + 1),
            real_write(data)))
        groups = [_columnar_group(ASCII[:5]), _columnar_group(LATIN[:5]),
                  _group(900, n=2)]
        for g in groups:
            assert f.send(g)
        f.flush_all()
        assert calls == {"append_group": 0, "write": 1}
        assert len(out.read_bytes().splitlines()) == 12
        f.stop()

    def test_a_failed_native_write_is_the_same_one_drop(self, tmp_path):
        led = ledger.enable()
        f, out = _flusher(tmp_path)
        f.file_path = str(tmp_path)          # open() of a directory fails
        assert f.send(_columnar_group(ASCII))
        f.flush_all()
        row = led.snapshot()["p"]
        assert row[ledger.B_DROP]["events"] == 40
        assert list(row[ledger.B_DROP]["tags"]) == ["flush_write_failed"]
        assert ledger.B_SEND_OK not in row
        f.file_path = str(out)               # and the sender lives on
        assert f.send(_columnar_group(ASCII))
        f.flush_all()
        assert len(out.read_bytes().splitlines()) == 40
        f.stop()

    def test_the_two_halves_keep_their_spans(self, tmp_path):
        f, out = _flusher(tmp_path)
        t = trace.enable()
        try:
            assert f.send(_columnar_group(ASCII))
            f.flush_all()
        finally:
            f.stop()
            spans = t.finished_spans()
            trace.disable()
        (ser,) = [s for s in spans if s.name == "flusher.serialize"]
        (wr,) = [s for s in spans if s.name == "flusher.write"]
        want = {"flusher": "flusher_file", "groups": 1, "events": 40,
                "nbytes": out.stat().st_size}
        for sp in (ser, wr):
            assert {k: v for k, v in sp.attrs.items()
                    if k not in ("cpu_s", "tid")} == want
        # one native call, one stretch of the sender's CPU clock: the
        # pair's CPU seconds are on the serialize span
        assert ser.attrs["cpu_s"] > 0 and wr.attrs["cpu_s"] is None
        assert ser.attrs["tid"] == wr.attrs["tid"]
        assert ser.parent_id is None and wr.parent_id is None
        assert ser.duration_s > 0 and wr.duration_s > 0
        assert wr.start_wall >= ser.start_wall + ser.duration_s - 1e-6


# ---------------------------------------------------------------------------
# the ledger's auditor and /debug/status, through a live pipeline

def _build_pipeline(tmp_path, name):
    pqm = ProcessQueueManager()
    mgr = CollectionPipelineManager(pqm, SenderQueueManager())
    runner = ProcessorRunner(pqm, mgr, thread_count=1)
    runner.init()
    out = tmp_path / f"{name}.jsonl"
    diff = ConfigDiff()
    diff.added[name] = {
        "inputs": [{"Type": "input_static_file_onetime",
                    "FilePaths": ["/nonexistent"]}],
        "processors": [{"Type": "processor_parse_regex_tpu",
                        "Regex": r"(\w+):(\d+)", "Keys": ["src", "seq"]}],
        "flushers": [{"Type": "flusher_file", "FilePath": str(out),
                      "MinSizeBytes": 1}],
    }
    mgr.update_pipelines(diff)
    return pqm, mgr, runner, mgr.find_pipeline(name), out


def _raw_group(payload: bytes) -> PipelineEventGroup:
    g = PipelineEventGroup()
    g.add_raw_event(1).set_content(g.source_buffer.copy_string(payload))
    return g


class TestThroughALivePipeline:
    def test_auditor_defers_while_a_write_is_stalled(self, tmp_path):
        """The case Batcher._emitting_events exists for, one station on:
        the batch has left the batcher, the ledger stands still, and the
        only counter that holds the events is the sender's."""
        led = ledger.enable()
        pqm, mgr, runner, p, out = _build_pipeline(tmp_path, "stall")
        f = p.flushers[0].plugin
        gate = _Gate(f)
        try:
            assert pqm.push_queue(p.process_queue_key,
                                  _raw_group(b"a:1\nb:2\nc:3\n"))
            assert gate.entered.wait(WAIT)
            assert wait_for(lambda: f.batcher.pending_events() == 0
                            and runner.in_hand_count() == 0, timeout=WAIT)
            assert f.inflight_events() == 3
            assert ledger.live_inflight() == 3
            aud = ConservationAuditor(led, interval_s=0.01)
            for _ in range(4):               # a still ledger, residual 3
                assert aud.audit_once() == {}
            assert aud.quiesced_audits_total == 0
            assert aud.residual_alarms_total == 0
            gate.release.set()
            snap = ledger.assert_conserved(timeout=WAIT)
            assert snap["stall"][ledger.B_SEND_OK]["events"] == 3
            assert ledger.live_inflight() == 0
        finally:
            gate.release.set()
            runner.stop()
            mgr.stop_all()
        assert len(out.read_text().splitlines()) == 3

    def test_debug_status_has_a_flush_section_per_sink(self, tmp_path):
        assert "flush" in exposition.STATUS_SECTIONS
        assert "flush" not in exposition.collect_status()   # no such sink
        pqm, mgr, runner, p, out = _build_pipeline(tmp_path, "st")
        try:
            for i in range(3):
                assert pqm.push_queue(p.process_queue_key,
                                      _raw_group(b"a:%d\n" % i))
            assert wait_for(lambda: len(_lines(out)) == 3, timeout=WAIT)
            doc = exposition.collect_status()["flush"]
            assert list(doc) == ["st/flusher_file/0"]
            st = doc["st/flusher_file/0"]
            assert set(st) == {"batches_total", "offloaded_total",
                               "enqueue_blocked_total",
                               "enqueue_blocked_seconds", "depth",
                               "depth_max"}
            assert wait_for(lambda: exposition.collect_status()["flush"][
                "st/flusher_file/0"]["offloaded_total"] == 3, timeout=WAIT)
            assert st["batches_total"] == 3 and st["depth_max"] >= 1
            assert st["enqueue_blocked_total"] == 0
        finally:
            runner.stop()
            mgr.stop_all()


def _lines(path):
    return path.read_text().splitlines() if path.exists() else []


# ---------------------------------------------------------------------------
# flusher_stdout keeps the inline flush

class TestStdoutStaysInline:
    def test_stdout_has_written_when_send_returns(self):
        f = FlusherStdout()
        assert f.init({}, PluginContext(pipeline_name="p"))
        f._stream = io.StringIO()
        before = threading.active_count()
        assert f.send(_group(3))
        assert json.loads(f._stream.getvalue())["seq"] == "3"
        assert threading.active_count() == before      # no sender thread
        f.stop()

    @pytest.mark.parametrize("attr", ["inflight_events", "flush_status",
                                      "_sender"])
    def test_stdout_owns_no_sender(self, attr):
        # the flusher's type decides, no switch: nothing to probe here
        assert not hasattr(FlusherStdout(), attr)
        assert hasattr(FlusherFile(), attr)
