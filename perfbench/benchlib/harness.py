"""One run of one cell: set-up, warm-up, the measured window, the drain, the
comparison, the metrics, the result line.

Order of a run (all times on CLOCK_MONOTONIC, shared by every process here):

  set-up    native library (built once per checkout), configuration
            directory, tailer process, agent child (the launcher), backend
            up, warm-up traffic settled in the sink           → ``setup_s``
  window    ``--seconds`` of the cell's traffic; /proc CPU read at both ends
            and once a second between; with ``--trace 1`` a jax.profiler
            trace of the agent's process, its spans, and /debug/ledger polls
  drain     every line written is followed until its fate is settled or the
            traffic's ``drain_limit_s`` passes; the ledger must come to rest
  after     the agent is stopped (its device's peak memory is read on the way
            out), the tailer finishes, the comparison runs, metrics are read

The observations (``obs``) handed to the metric readers are the dict built
in ``_run`` once the agent has gone.
"""

from __future__ import annotations

import glob
import json
import mmap
import os
import shutil
import struct
import subprocess
import sys
import threading
import time

import numpy as np

from . import agent as agentmod
from . import check, observe, schedule, spec, tracered
from .generator import Generator

#: whole-run budget: a first run in a checkout compiles
DEADLINE_S = 1100.0
MIB = 1 << 20


class RunFailure(Exception):
    """The run cannot produce a result line; the message is the reason."""


def _say(msg: str) -> None:
    print(msg, flush=True)


class Progress:
    """The tailer's progress file: last line settled, rows seen."""

    def __init__(self, path: str, deadline: float):
        while not os.path.exists(path) or os.path.getsize(path) < 16:
            if time.monotonic() > deadline:
                raise RunFailure("the tailer never started")
            time.sleep(0.005)
        self._f = open(path, "rb")
        self._m = mmap.mmap(self._f.fileno(), 16, access=mmap.ACCESS_READ)

    def read(self):
        return struct.unpack("<qq", self._m[:16])

    def settled_bytes(self, line_bytes: int) -> int:
        return (self.read()[0] + 1) * line_bytes

    def close(self) -> None:
        self._m.close()
        self._f.close()


def _split_cores(harness_cores: int):
    """(agent's cores, the benchmark's cores): the last ``harness_cores`` of
    this process's cores go to the generator and the tailer, the rest to the
    agent; no split on a machine too small for one."""
    cores = sorted(os.sched_getaffinity(0))
    if harness_cores <= 0 or len(cores) < harness_cores + 4:
        return None, None
    return cores[:-harness_cores], cores[-harness_cores:]


def _wait(cond, limit_s: float, alive, what: str, step: float = 0.01) -> bool:
    t_end = time.monotonic() + limit_s
    while not cond():
        alive(what)
        if time.monotonic() > t_end:
            return False
        time.sleep(step)
    return True


def _ledger_row(ledger: dict, pipeline: str) -> dict:
    return ((ledger or {}).get("pipelines") or {}).get(pipeline) or {}


def _events(row: dict, boundary: str) -> int:
    return int(((row.get("boundaries") or {}).get(boundary) or {})
               .get("events", 0))


def run_cell(args, t_start: float, work_dir: str = None) -> int:
    """One run of ``args.workload``; its files go under ``work_dir`` (the
    checkout's ``.perfbench_runs`` unless a test says otherwise) and are
    deleted when it ends."""
    bm = spec.load_benchmark()
    cell = spec.find_cell(bm, args.workload)
    cfg = spec.load_config(bm, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    root = spec.ROOT
    if not os.path.isdir(os.path.join(root, "loongcollector_tpu")):
        raise RunFailure(f"{root} holds no loongcollector_tpu package: "
                         f"nothing to measure")
    seed, seconds, traced = args.seed, float(args.seconds), bool(args.trace)
    deadline = t_start + DEADLINE_S
    epoch_lo = time.time()

    run_dir = os.path.join(
        work_dir or os.path.join(root, ".perfbench_runs"),
        f"{cell['name']}-{seed}-{int(traced)}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    try:
        return _run(args, bm, cell, cfg, traffic, root, run_dir, seed,
                    seconds, traced, t_start, deadline, epoch_lo)
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)


def _run(args, bm, cell, cfg, traffic, root, run_dir, seed, seconds, traced,
         t_start, deadline, epoch_lo) -> int:
    source = spec.load_module("sources", cfg["source"]["kind"]).make(
        cfg["source"], seed)
    reference = spec.load_module("references", cfg["reference"]["kind"]) \
        .make(cfg["reference"])
    line_bytes = source.line_bytes
    pipeline = cfg["pipeline_name"]
    build_s = agentmod.build_native(root)

    agent_cores, my_cores = _split_cores(int(traffic.get("harness_cores", 0)))
    if my_cores:
        os.sched_setaffinity(0, my_cores)

    log_path = os.path.join(run_dir, "input.log")
    sink_path = os.path.join(run_dir, "sink.jsonl")
    cfg_dir = os.path.join(run_dir, "config")
    data_dir = os.path.join(run_dir, "data")
    app_config = agentmod.write_config(cfg, cfg_dir, data_dir, log_path,
                                       sink_path, traced)

    tail_spec = os.path.join(run_dir, "tail.json")
    with open(tail_spec, "w") as f:
        json.dump({"run_dir": run_dir, "sink": sink_path, "seed": seed,
                   "config": {"source": cfg["source"],
                              "reference": cfg["reference"]},
                   "sample_share": traffic["check_sample_share"],
                   "fault": args.fault if args.fault in check.STREAM_FAULTS
                   else None}, f)
    tailer = subprocess.Popen(
        [sys.executable, os.path.join(spec.BENCH_DIR, "benchlib", "tailer.py"),
         tail_spec], cwd=root)
    agent = None
    gen = None
    progress = None
    try:
        agent = agentmod.Agent(root, run_dir, cfg_dir, data_dir,
                               agentmod.agent_env(cfg, traced),
                               cores=agent_cores, deadline=deadline)
        agent.wait_endpoint()
        dev = agent.wait_backend()
        t_backend = time.monotonic()
        pinned_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
        if dev["platform"] == "cpu" and not pinned_cpu:
            raise RunFailure("the agent computes on the CPU and nobody pinned "
                             "it there: no accelerator")
        if dev["device_count"] < cell["chips"]:
            raise RunFailure(f"the cell asks for {cell['chips']} chip(s), JAX "
                             f"found {dev['device_count']}")

        progress = Progress(os.path.join(run_dir, "tail.progress"), deadline)
        gen = Generator(source, log_path)

        keep = check.keep_mask(source, reference)
        memo = [0, -1]

        def last_kept(n_lines: int) -> int:
            """The last line below ``n_lines`` that reaches the sink."""
            if memo[0] != n_lines:
                lo = max(0, n_lines - 65536)
                kept = np.flatnonzero(
                    keep[source.template_of(lo, n_lines - lo)])
                memo[:] = [n_lines, lo + int(kept[-1]) if kept.size else -1]
            return memo[1]

        def settled_all() -> bool:
            return progress.read()[0] >= last_kept(gen.next_seq)

        # -- warm-up ---------------------------------------------------------
        warm = traffic["warmup"]
        stop_closed = threading.Event()
        closed_thread = None
        if warm.get("backlog_MiB"):
            gen.write(int(warm["backlog_MiB"] * MIB // line_bytes))
            if not _wait(settled_all, DEADLINE_S, agent.check_alive,
                         "warming up (the backlog)"):
                raise RunFailure("the warm-up backlog never settled")
        if traffic["mode"] == "open" and warm.get("schedule_s"):
            due, n = schedule.open_schedule(traffic, float(warm["schedule_s"]),
                                            line_bytes, seed + 1)
            gen.run_open(due, n, time.monotonic() + 0.05)
            if not _wait(settled_all, 120, agent.check_alive,
                         "warming up (the schedule)"):
                raise RunFailure("the warm-up schedule never settled")
        if traffic["mode"] == "closed":
            lead = int(traffic["lead_MiB"] * MIB)
            closed_thread = threading.Thread(
                target=gen.run_closed, name="perfbench-gen", daemon=True,
                args=(lead, int(traffic["write_lines"]),
                      lambda: progress.settled_bytes(line_bytes), stop_closed))
            closed_thread.start()
            want = gen.offset + int(warm.get("closed_MiB", 64) * MIB)
            if not _wait(lambda: progress.settled_bytes(line_bytes) >= want,
                         120, agent.check_alive, "warming up (closed loop)"):
                raise RunFailure("the closed loop never got going")
        time.sleep(float(warm.get("idle_s", 0.0)))

        status0 = agent.get("/debug/status")
        ledger0 = agent.get("/debug/ledger")
        metrics0 = agentmod.parse_metrics(agent.get_text("/metrics"))

        # -- the window ------------------------------------------------------
        proc = []
        polls = []
        open(os.path.join(run_dir, "tail.arm"), "w").close()
        proc0 = agent.sample()
        t0 = proc0[0]
        t1 = t0 + seconds
        gen_thread = None
        if traffic["mode"] == "open":
            due, n = schedule.open_schedule(traffic, seconds, line_bytes, seed)
            gen_thread = threading.Thread(target=gen.run_open,
                                          name="perfbench-gen", daemon=True,
                                          args=(due, n, t0))
            gen_thread.start()
        proc.append(proc0)
        trace_ends: dict = {}
        trace_thread = None
        if traced:
            trace_thread = threading.Thread(
                target=_trace_slice, name="perfbench-trace", daemon=True,
                args=(agent, traffic, t0, seconds, trace_ends))
            trace_thread.start()
        poll_gap = 1.0 / float(traffic.get("ledger_poll_hz", 2)) \
            if traced else None
        next_sample, next_poll = t0 + 1.0, t0
        while True:
            now = time.monotonic()
            wake = min(t1, next_sample,
                       next_poll if poll_gap else t1)
            if now < wake:
                time.sleep(wake - now)
                continue
            if now >= t1:
                break
            if now >= next_sample:
                proc.append(agent.sample())
                next_sample += 1.0
            if poll_gap and now >= next_poll:
                row = _ledger_row(agent.get("/debug/ledger"), pipeline)
                polls.append((time.monotonic(), _events(row, "ingest")))
                next_poll += poll_gap
            agent.check_alive("serving the window")
        proc1 = agent.sample()
        proc.append(proc1)
        t1 = proc1[0]
        stop_closed.set()
        for th in (closed_thread, gen_thread, trace_thread):
            if th is not None:
                th.join(timeout=float(traffic["drain_limit_s"]) + 300)
                if th.is_alive():
                    raise RunFailure(f"{th.name} did not finish within the "
                                     f"drain limit")
        if traced and "stop" not in trace_ends:
            raise RunFailure(f"the trace was not taken: "
                             f"{trace_ends.get('error')}")

        # -- the drain -------------------------------------------------------
        written = gen.next_seq
        drained = _wait(settled_all, float(traffic["drain_limit_s"]),
                        agent.check_alive, "draining", 0.005)
        ledger1 = None
        quiesce_t = None

        def at_rest() -> bool:
            nonlocal ledger1, quiesce_t
            ledger1 = agent.get("/debug/ledger")
            row = _ledger_row(ledger1, pipeline)
            if _events(row, "ingest") >= written and row.get("residual") == 0 \
                    and ledger1.get("inflight_live") == 0:
                quiesce_t = time.monotonic()
                return True
            return False
        rested = _wait(at_rest, 30.0 if drained else 1.0, agent.check_alive,
                       "waiting for the ledger to come to rest", 0.1)
        status1 = agent.get("/debug/status")
        metrics1 = agentmod.parse_metrics(agent.get_text("/metrics"))
        rc = agent.stop()
        agent_log = agent.log_text()
    except BaseException:
        if agent is not None:
            agent.stop()
            sys.stderr.write("---- agent log (last lines) ----\n" + "\n".join(
                agent.log_text().splitlines()[-40:]) + "\n")
            sys.stderr.write("agent rss MB by second: " + json.dumps(
                [round(p[2] / 1e6) for p in locals().get("proc", [])]) + "\n")
        raise
    finally:
        open(os.path.join(run_dir, "tail.stop"), "w").close()
        try:
            tailer.wait(timeout=120)
        except subprocess.TimeoutExpired:
            tailer.kill()
            tailer.wait()
        if gen is not None:
            gen.close()
        if progress is not None:
            progress.close()

    # -- after the agent: what the tailer saw, the comparison ----------------
    if tailer.returncode != 0:
        raise RunFailure(f"the tailer exited with code {tailer.returncode}")
    tz = np.load(os.path.join(run_dir, "tail.npz"))
    tail = {k: tz[k] for k in tz.files}
    tail["last_seq"] = np.maximum.accumulate(tail["last_seq"]) \
        if tail["last_seq"].size else tail["last_seq"]
    rows, _nbytes, bad_seq, bad_newlines, carry = tail["counts"].tolist()
    seen = int(tail["last_seq"][-1]) if tail["last_seq"].size else -1
    if rested and quiesce_t is not None and seen >= last_kept(written) \
            and seen < written - 1:
        # the lines after the last kept one (a filter's drops) are settled
        # once every kept line is in the sink and the ledger is at rest with
        # every line taken in and none in flight
        tail["t"] = np.append(tail["t"], quiesce_t)
        tail["last_seq"] = np.append(tail["last_seq"], written - 1)
        tail["rows_end"] = np.append(tail["rows_end"], rows)
        tail["bytes_end"] = np.append(tail["bytes_end"], tail["bytes_end"][-1])
    device_path = os.path.join(run_dir, "device.json")
    device = spec.load_json(device_path) if os.path.exists(device_path) else {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"], "memory_peak_bytes": 0}

    obs = {
        # what the metric readers may read (README.md, "Adding things")
        "cell": cell, "config": cfg, "traffic": traffic, "seed": seed,
        "seconds": seconds, "traced": traced, "line_bytes": line_bytes,
        "app_config": app_config, "t_start": t_start, "t0": t0, "t1": t1,
        "writes": gen.writes(), "tail": tail,
        "proc": proc, "proc0": proc0, "proc1": proc1, "polls": polls,
        "status0": status0, "status1": status1,
        "ledger0": ledger0, "ledger1": ledger1,
        "metrics0": metrics0, "metrics1": metrics1,
        "device": device, "peaks": spec.load_peaks(),
        "runtime_rss_bytes": (status1.get("device") or {})
        .get("runtime_rss_bytes", 0),
        "spans": None, "trace": None, "breakdown": None, "slice": None,
    }
    if traced:
        _read_trace(obs, run_dir, trace_ends["mark"], trace_ends["stop"])

    cmp_ = check.compare_samples(run_dir, tail, source, reference, epoch_lo)
    row1 = _ledger_row(ledger1, pipeline)
    expect_rows = int(np.count_nonzero(
        keep[source.template_of(0, written)])) if written else 0
    settled_end = int(tail["last_seq"][-1]) + 1 if tail["last_seq"].size else 0
    if args.fault == "residual":           # the control: a ledger that leaks
        row1 = dict(row1, residual=(row1.get("residual") or 0) + 1)
    routing = ((status1.get("device") or {}).get("routing") or {})
    checks = {
        "unsettled_lines": max(written - settled_end, 0),
        "rows_off_sequence": bad_seq + bad_newlines + (1 if carry else 0),
        "rows_missing_or_extra": abs(rows - expect_rows),
        "records_differ": cmp_["bad_record"],
        "times_differ": cmp_["bad_time"],
        "records_short": max(check.MIN_RECORDS - cmp_["compared"], 0),
        "ledger_residual": abs(row1.get("residual", 1)
                               if row1.get("residual") is not None else 1),
        "ledger_not_at_rest": 0 if rested else 1,
        "ledger_send_ok_gap": abs(_events(row1, "send_ok") - rows),
        "kernel_fallbacks": int(routing.get("kernel_fallbacks_total", 0)),
        "agent_complaints": len(agentmod.log_complaints(agent_log)),
        "agent_exit_code": abs(rc),
    }
    checks = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    correct = check.verdict(checks)

    # -- numbers -------------------------------------------------------------
    section = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in spec.metrics_of_cell(bm, cell["name"], section):
        value = spec.load_module("metrics", m["name"]).read(obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if traffic["mode"] == "open":
        attempted = int(observe.latencies_ms(obs).size)
        failed = int(np.count_nonzero(np.isinf(observe.latencies_ms(obs))))
    else:
        failed = checks["unsettled_lines"]["value"]
        attempted = observe.delivered_bytes(obs) // line_bytes + failed
    failed += bad_seq + cmp_["bad_record"] + cmp_["bad_time"]

    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if traced:
        busy = observe.device_busy(obs)
        if busy is not None:
            result["device"]["busy_s"], result["device"]["window_s"] = busy
            result["breakdown"] = obs["breakdown"]
    result["checks"] = checks

    _report(obs, cmp_, tail, build_s, t_backend, agent_log, args)
    sys.stderr.write(check.render(checks) + "\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _trace_slice(agent, traffic: dict, t0: float, seconds: float,
                 ends: dict) -> None:
    """Take the profiler trace of a slice of the window: the traffic file's
    ``trace_s`` seconds from ``trace_offset_s`` into it (a fused program runs
    thousands of device operations per dispatch, and every second of its
    trace costs the profiler's stop the better part of a minute).  The
    launcher's answers go into ``ends``."""
    length = min(float(traffic.get("trace_s", seconds)), seconds)
    offset = min(float(traffic.get("trace_offset_s", 0.0)), seconds - length)
    try:
        time.sleep(max(t0 + offset - time.monotonic(), 0.0))
        ends["mark"] = agent.request("trace.start", "trace.started")
        time.sleep(max(t0 + offset + length - time.monotonic(), 0.0))
        ends["stop"] = agent.request("trace.stop", "trace.stopped", 300)
    except agentmod.AgentFailure as e:
        ends["error"] = str(e)


def _read_trace(obs: dict, run_dir: str, mark: dict, stop: dict) -> None:
    """The traced slice's spans and device events into ``obs``: the slice
    (``slice``, on the common clock), the spans that ran inside it, the
    device operations, both on the spans' clock."""
    a, b = mark["mark_perf_ns"] / 1e9, stop["stop_perf_ns"] / 1e9
    obs["slice"] = (a, b)
    spans = []
    path = os.path.join(run_dir, "spans.jsonl")
    if os.path.exists(path):
        with open(path) as f:
            for ln in f:
                s = json.loads(ln)
                if s[1] >= a and s[1] + s[2] <= b:
                    spans.append(s)
    obs["spans"] = spans
    found = glob.glob(os.path.join(run_dir, "xplane", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    if not found:
        return
    events_path = os.path.join(run_dir, "events.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(
        spec.BENCH_DIR, "benchlib", "xplane_dump.py"), found[0], events_path],
        env=env, capture_output=True, text=True, timeout=240)
    if r.returncode != 0:
        sys.stderr.write("xplane_dump failed:\n" + r.stderr[-2000:] + "\n")
        return
    doc = spec.load_json(events_path)
    names = doc["names"]
    events = [[e[0], e[1], names[e[2]], e[3], e[4]] for e in doc["events"]]
    mark_ns = tracered.mark_start_ns(events)
    if mark_ns is None:
        return

    def to_seconds(ns):
        return (np.asarray(ns) - mark_ns) / 1e9 + a
    lo_ns, hi_ns = mark_ns, mark_ns + (b - a) * 1e9
    obs["trace"] = {"events": events, "lo_ns": lo_ns, "hi_ns": hi_ns}
    ops = [o for o in tracered.device_ops(events) if lo_ns <= o[2] < hi_ns]
    obs["breakdown"] = {
        "device_ops": tracered.top(tracered.op_seconds(ops)),
        "idle_gaps": tracered.top(tracered.idle_gaps_by_span(
            ops, spans, a, b, to_seconds)),
    }


def _report(obs, cmp_, tail, build_s, t_backend, agent_log, args) -> None:
    """Earlier lines of the output: where set-up went, the per-second series,
    the agent's own CPU samples and its watchdog lines.  Never the last."""
    t0, t1 = obs["t0"], obs["t1"]
    _say(f"cell {obs['cell']['name']} seed {obs['seed']} seconds "
         f"{obs['seconds']} trace {int(obs['traced'])} fault {args.fault}")
    _say(f"set-up: native build {build_s:.2f} s, backend up after "
         f"{t_backend - obs['t_start']:.2f} s, window opened after "
         f"{t0 - obs['t_start']:.2f} s")
    edges = np.arange(t0, t1 + 1e-9, 1.0)
    if edges.size > 1 and tail["t"].size:
        settled = np.array([observe.settled_lines_at(obs, e) for e in edges])
        mbps = np.diff(settled) * obs["line_bytes"] / 1e6 / np.diff(edges)
        _say("series delivered_MBps " + json.dumps(
            [round(float(x), 2) for x in mbps]))
    cores = observe.cpu_series(obs)
    _say("series agent_cpu_cores " + json.dumps(
        [round(float(x), 3) for x in cores]))
    rss = [round((s[2] - obs["runtime_rss_bytes"]) / 1e6) for s in obs["proc"]]
    _say("series agent_rss_MB " + json.dumps(rss))
    routing = ((obs["status1"] or {}).get("device") or {}).get("routing") or {}
    probe = routing.get("probe") or {}
    share = observe.device_row_share(obs)
    _say(f"routing: device_row_share {share}; the program's probe put the "
         f"crossover at {probe.get('crossover_bytes')} bytes (latency "
         f"{probe.get('latency_s')} s, {probe.get('bandwidth_Bps')} B/s); "
         f"forced: {routing.get('forced')}")
    if share is not None and share < 0.9:
        _say("routing: under nine tenths of the window's rows crossed the "
             "device — groups under the crossover stay on the host tiers")
    watchdog = [ln for ln in agent_log.splitlines() if "watchdog" in ln]
    _say(f"agent log: {len(watchdog)} watchdog line(s)"
         + ("".join("\n  " + ln for ln in watchdog[:5])))
    w = obs["writes"]
    due_in = (w["due"] >= t0) & (w["due"] < t1)
    late = ((w["done"] - w["due"]) * 1e3)[due_in]
    if obs["traffic"]["mode"] == "open" and late.size:
        big = w["count"][due_in] == w["count"][due_in].max()
        _say(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} p99 "
             f"{np.percentile(late, 99):.3f} max {late.max():.3f}; of the "
             f"largest writes: {np.round(late[big][-8:], 1).tolist()}")
    _say(f"generator: {w['count'].sum()} lines in {w['count'].size} writes; "
         f"tailer: {tail['counts'][0]} rows in {tail['t'].size} reads; "
         f"compared {cmp_['compared']} records field by field"
         + ("".join("\n  " + s for s in cmp_["first_bad"])))
    for row in tail["first_bad"].tolist():
        _say(f"  sink row {row[0]}: expected line {row[1]}, found {row[2]}")
