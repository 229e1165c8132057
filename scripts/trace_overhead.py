#!/usr/bin/env python
"""Tracing-overhead smoke gate (wired into scripts/lint.sh).

The loongtrace contract (docs/observability.md) is that DISABLED tracing
costs one module-global read + branch per hook.  This script proves it two
ways and exits non-zero when the contract regresses:

1. **Per-hook microbench** — ns/call of the disabled hooks
   (`trace.is_active`, `trace.event`, `trace.start_span`) with a generous
   absolute ceiling: a regression that makes the disabled path allocate
   or take locks blows through it immediately.

2. **10k-event synthetic pipeline** — the real instrumented path
   (ProcessorInstance split stage + SLS serialization, no threads so the
   measurement is deterministic) timed in two configurations,
   interleaved, best-of-N each:

     * ``disabled``  — hooks as shipped, tracer off (the production path);
     * ``baseline``  — the same hooks monkeypatched to bare no-op
       lambdas, i.e. the cheapest conceivable "tracing compiled out".

   Gate: disabled must be within 5% of baseline.  The tracer-enabled
   time is also measured and reported (informational — enabling tracing
   MAY cost; disabling it MUST NOT).

3. **The per-group sites** — the same paired gate over the sites that fire
   once per group, dispatch, flush or file-server round rather than per
   stage: `LogFileReader.read`, `FlusherFile` send → flush,
   `DevicePlane.submit` → `DeviceFuture.result()` with the pack stopwatch
   handed to `xprof.note_dispatch`, and `FileServer._round` with its
   always-on counters — rounds that find nothing, and one that moves 64
   groups (enabled: `input.file.round` with a read, a push and a
   checkpoint span a group under it).

The enabled/baseline figures are the cost of tracing ON (every span takes
two readings of the wall clock and two of its thread's CPU clock): compare
them between two commits only from one call on one machine.
"""

import sys
import time

sys.path.insert(0, __import__("os").path.join(
    __import__("os").path.dirname(__file__), ".."))

N_EVENTS = 10_000
REPEATS = 9
MAX_DISABLED_OVER_BASELINE = 1.05      # the 5% gate
MAX_HOOK_NS = 2_000                    # catastrophic-regression ceiling


def bench_hooks():
    from loongcollector_tpu import trace
    trace.disable()
    out = {}
    for label, fn in (("is_active", trace.is_active),
                      ("event", lambda: trace.event("x")),
                      ("start_span", lambda: trace.start_span("x"))):
        n = 200_000
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            best = min(best, (time.perf_counter() - t0) / n)
        out[label] = best * 1e9
    return out


def make_runner():
    from loongcollector_tpu.models import PipelineEventGroup, SourceBuffer
    from loongcollector_tpu.pipeline.plugin.instance import ProcessorInstance
    from loongcollector_tpu.pipeline.plugin.interface import PluginContext
    from loongcollector_tpu.pipeline.serializer.sls_serializer import \
        SLSEventGroupSerializer
    from loongcollector_tpu.processor.split_log_string import \
        ProcessorSplitLogString
    inst = ProcessorInstance(ProcessorSplitLogString(), "split/overhead")
    assert inst.init({}, PluginContext("overhead"))
    ser = SLSEventGroupSerializer()
    line = b"2024-01-02 03:04:05 INFO request handled ok\n"
    data = line * N_EVENTS

    def run_timed():
        sb = SourceBuffer(len(data) + 64)
        g = PipelineEventGroup(sb)
        g.add_raw_event(1).set_content(sb.copy_string(data))
        t0 = time.perf_counter()
        inst.process([g])
        ser.serialize([g])
        dt = time.perf_counter() - t0
        assert len(g) == N_EVENTS
        return dt

    return inst, run_timed


def make_sites(tmp_dir: str):
    """One pass over the per-group sites, 64 of each: a 64 KiB chunk read,
    a group sent through flusher_file (every send flushes), a dispatch
    materialised, a file-server round that finds nothing new, and one
    round that reads 64 chunks and hands each to a queue."""
    import os
    import numpy as np
    from loongcollector_tpu import trace
    from loongcollector_tpu.flusher.file import FlusherFile
    from loongcollector_tpu.input.file.file_server import (FileServer,
                                                           _ConfigState)
    from loongcollector_tpu.input.file.polling import FileDiscoveryConfig
    from loongcollector_tpu.input.file.reader import LogFileReader
    from loongcollector_tpu.models import PipelineEventGroup
    from loongcollector_tpu.ops import xprof
    from loongcollector_tpu.ops.device_plane import DevicePlane
    from loongcollector_tpu.pipeline.plugin.instance import FlusherInstance
    from loongcollector_tpu.pipeline.plugin.interface import PluginContext
    n = 64
    log_path = os.path.join(tmp_dir, "in.log")
    line = b"2024-01-02 03:04:05 INFO request handled ok\n"
    with open(log_path, "wb") as f:
        f.write(line * (n * 65536 // len(line) + 1))
    flusher = FlusherFile()
    assert flusher.init({"FilePath": os.path.join(tmp_dir, "sink.jsonl"),
                         "MinSizeBytes": 1}, PluginContext("overhead"))
    sink = FlusherInstance(flusher, "flusher_file/overhead")
    plane = DevicePlane(budget_bytes=1 << 20)
    rows = np.zeros((256, 128), np.uint8)
    server = FileServer()
    server._configs["overhead"] = _ConfigState(
        "overhead", FileDiscoveryConfig([log_path]), queue_key=1,
        tail_existing=False)
    server._round()                    # opens the reader at the file's end

    class TakesAll:
        def is_valid_to_push(self, key):
            return True

        def push_queue(self, key, group):
            return True

        def get_queue(self, key):
            return None
    mover = FileServer()
    mover.process_queue_manager = TakesAll()
    moving = mover._configs["overhead"] = _ConfigState(
        "overhead", FileDiscoveryConfig([log_path]), queue_key=1,
        tail_existing=True, chunk_size=65536)
    mover._round()                     # opens the reader at the file's start

    def run_timed():
        reader = LogFileReader(log_path, chunk_size=65536,
                               presplit_lines=True)
        groups = []
        t0 = time.perf_counter()
        for _ in range(n):
            groups.append(reader.read())
        for g in groups:
            sink.send(g)
        flusher.flush_all()            # the sender thread's spans too
        for _ in range(n):
            t_pack = (time.perf_counter()
                      if xprof.is_active() or trace.is_active() else None)
            fut = plane.submit(lambda r: r.sum(axis=1), (rows,), rows.nbytes)
            xprof.note_dispatch(fut, "overhead", "256x128", t_pack,
                                None if t_pack is None else 0.0)
            fut.result()
        for _ in range(n):
            server._round()
        for r in moving.readers.values():
            r.offset = 0               # the same 64 chunks again
        reads = mover.stats.reads_total
        mover._round()
        dt = time.perf_counter() - t0
        assert mover.stats.reads_total - reads == n
        reader.close()
        assert all(g is not None for g in groups)
        return dt

    def close():
        flusher.stop()
        sink.metrics.mark_deleted()

    return run_timed, close


def paired_rounds(run_timed):
    """Paired rounds: on a shared single core, absolute ms-scale timings
    drift more than the 5% budget (co-tenant steal), but a REAL
    disabled-path regression is systematic — it shows up in EVERY
    baseline/disabled pair measured back-to-back.  So the gate is the
    MINIMUM paired ratio across rounds: if even one round ran the
    shipped hooks within 5% of the no-op baseline, the hooks are one
    branch; sustained overhead fails all rounds and trips the gate.
    Returns the disabled/baseline and enabled/baseline ratios."""
    import gc
    from loongcollector_tpu import trace
    noop_active = lambda: False                       # noqa: E731
    noop_none = lambda *a, **k: None                  # noqa: E731
    real = (trace.is_active, trace.start_span, trace.active_tracer)

    def set_baseline():
        trace.disable()
        trace.is_active = noop_active
        trace.start_span = noop_none
        trace.active_tracer = noop_none

    def set_disabled():
        trace.is_active, trace.start_span, trace.active_tracer = real
        trace.disable()

    def set_enabled():
        trace.is_active, trace.start_span, trace.active_tracer = real
        trace.enable()

    dis_ratios, en_ratios = [], []
    try:
        run_timed()                                   # warm the path
        for i in range(REPEATS):
            pair = [("baseline", set_baseline), ("disabled", set_disabled)]
            if i % 2:                                 # kill position bias
                pair.reverse()
            times = {}
            for name, setup in pair + [("enabled", set_enabled)]:
                setup()
                gc.collect()
                times[name] = run_timed()
                trace.disable()
            dis_ratios.append(times["disabled"] / times["baseline"])
            en_ratios.append(times["enabled"] / times["baseline"])
    finally:
        trace.is_active, trace.start_span, trace.active_tracer = real
        trace.disable()
    return dis_ratios, en_ratios


def gate(label: str, dis_ratios, en_ratios) -> int:
    ratio = min(dis_ratios)
    print(f"{label}, {REPEATS} paired rounds: "
          f"disabled/baseline min={ratio:.3f} "
          f"median={sorted(dis_ratios)[len(dis_ratios) // 2]:.3f}  "
          f"enabled/baseline min={min(en_ratios):.3f} "
          f"median={sorted(en_ratios)[len(en_ratios) // 2]:.3f}")
    if ratio > MAX_DISABLED_OVER_BASELINE:
        print(f"FAIL: disabled-path overhead {(ratio - 1) * 100:.1f}% "
              f"> {(MAX_DISABLED_OVER_BASELINE - 1) * 100:.0f}% in every "
              "round — the disabled tracer must stay one branch per hook")
        return 1
    return 0


def main() -> int:
    hooks = bench_hooks()
    print("disabled hook cost (ns/call): "
          + ", ".join(f"{k}={v:.0f}" for k, v in hooks.items()))
    bad = {k: v for k, v in hooks.items() if v > MAX_HOOK_NS}
    if bad:
        print(f"FAIL: disabled hooks over {MAX_HOOK_NS} ns: {bad}")
        return 1

    inst, run_timed = make_runner()
    try:
        rc = gate(f"{N_EVENTS}-event synthetic pipeline",
                  *paired_rounds(run_timed))
    finally:
        inst.metrics.mark_deleted()
    if rc:
        return rc
    import tempfile
    with tempfile.TemporaryDirectory(prefix="trace_overhead_") as tmp:
        run_sites, close = make_sites(tmp)
        try:
            rc = gate("per-group sites (read, flush, result, rounds)",
                      *paired_rounds(run_sites))
        finally:
            close()
    if rc:
        return rc
    rc = smoke_multiworker()
    if rc:
        return rc
    print("trace overhead OK")
    return 0


def smoke_multiworker() -> int:
    """loongshard smoke (lint.sh runs this file with
    LOONG_PROCESS_THREADS=4): with the sharded plane active, a burst of
    multi-source groups must drain losslessly, in per-source order, and
    the runner must stop cleanly.  No-op when the env var is absent or 1
    (the single-worker path is what the paired rounds above measured)."""
    import os
    import time as _time
    if int(os.environ.get("LOONG_PROCESS_THREADS", "1") or "1") <= 1:
        return 0
    from loongcollector_tpu.pipeline.pipeline_manager import (
        CollectionPipelineManager, ConfigDiff)
    from loongcollector_tpu.pipeline.queue.process_queue_manager import \
        ProcessQueueManager
    from loongcollector_tpu.pipeline.queue.sender_queue import \
        SenderQueueManager
    from loongcollector_tpu.runner.processor_runner import (
        ProcessorRunner, resolve_thread_count)
    from loongcollector_tpu.models import PipelineEventGroup, SourceBuffer
    tc = resolve_thread_count()
    pqm = ProcessQueueManager()
    mgr = CollectionPipelineManager(pqm, SenderQueueManager())
    runner = ProcessorRunner(pqm, mgr, thread_count=tc)
    runner.init()
    diff = ConfigDiff()
    diff.added["overhead-shard"] = {
        "inputs": [{"Type": "input_static_file_onetime",
                    "FilePaths": ["/nonexistent"]}],
        "processors": [],
        "flushers": [{"Type": "flusher_blackhole"}],
    }
    mgr.update_pipelines(diff)
    p = mgr.find_pipeline("overhead-shard")
    bh = p.flushers[0].plugin
    n_groups, per_group = 48, 32
    line = b"2024-01-02 03:04:05 INFO shard smoke\n"
    try:
        for i in range(n_groups):
            payload = line * per_group
            sb = SourceBuffer(len(payload) + 64)
            g = PipelineEventGroup(sb)
            g.add_raw_event(1).set_content(sb.copy_string(payload))
            g.set_tag(b"__source__", b"smoke-%d" % (i % 6))
            deadline = _time.monotonic() + 20
            while not pqm.push_queue(p.process_queue_key, g):
                if _time.monotonic() > deadline:
                    print("FAIL: multi-worker smoke push starved")
                    return 1
                _time.sleep(0.002)
        deadline = _time.monotonic() + 30
        while bh.total_events < n_groups and \
                _time.monotonic() < deadline:
            _time.sleep(0.01)
        if bh.total_events < n_groups:
            print(f"FAIL: multi-worker smoke lost groups "
                  f"({bh.total_events}/{n_groups} reached the sink)")
            return 1
    finally:
        runner.stop()
        mgr.stop_all()
    print(f"multi-worker smoke OK ({tc} workers, {n_groups} groups)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
