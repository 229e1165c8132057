"""Prometheus-text self-telemetry endpoint (off by default).

Reference analogue: the reference agent exposes its internal metrics for
scraping next to the self-monitor pipelines; here a stdlib
ThreadingHTTPServer serves ``GET /metrics`` rendering every live
MetricsRecord in text exposition format v0.0.4:

  * counters  → ``loong_<name>`` (NOTE: the self-monitor drains counters
    with delta semantics on its own cadence, so scraped counter values
    are deltas since the last self-monitor send, not process-lifetime
    cumulatives — documented in docs/observability.md);
  * gauges    → ``loong_<name>``;
  * histograms→ full ``_bucket{le=...}`` / ``_sum`` / ``_count`` series
    plus pre-computed ``_p50/_p90/_p99`` gauges for humans;
  * record labels (pipeline, plugin_id, sink...) become metric labels,
    with ``category`` always present.

Rendering never resets anything — scraping is read-only and safe to run
concurrently with the self-monitor drain.

loongprof (ISSUE 5) grows the endpoint into the agent's debug surface:

  * ``/healthz``       — liveness: 200 + uptime / worker-count JSON;
  * ``/debug/status``  — running status JSON (pipelines, queue depths,
    worker backlogs, breaker states, device-budget utilization, flight
    ring counts), assembled from observe-only module handles — the
    endpoint never constructs a subsystem to report on it;
  * ``/debug/pprof``   — the active profiler's folded stacks
    (flamegraph input; a comment line when profiling is off);
  * ``/debug/flight``  — the live flight-recorder ring as JSON (the same
    document a crash dump writes);
  * anything else      — 404 (the metrics page answers ONLY /metrics).

Activation: ``LOONG_EXPO_PORT=<port>`` env (application start) or
programmatic ``ExpositionServer(port).start()``; binds 127.0.0.1 unless
``LOONG_EXPO_HOST`` widens it.
"""

from __future__ import annotations

import http.server
import json
import math
import os
import re
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..utils.logger import get_logger
from .metrics import WriteMetrics

log = get_logger("exposition")

ENV_PORT = "LOONG_EXPO_PORT"
ENV_HOST = "LOONG_EXPO_HOST"

_process_t0 = time.monotonic()

_PREFIX = "loong_"
_NAME_SAN = re.compile(r"[^a-zA-Z0-9_]")
_LABEL_SAN = re.compile(r"[^a-zA-Z0-9_]")


def _metric_name(raw: str) -> str:
    name = _NAME_SAN.sub("_", raw)
    if not name or name[0].isdigit():
        name = "_" + name
    return _PREFIX + name


def _label_str(labels: Dict[str, str], extra: str = "") -> str:
    parts = []
    for k in sorted(labels):
        key = _LABEL_SAN.sub("_", str(k))
        val = str(labels[k]).replace("\\", "\\\\").replace(
            '"', '\\"').replace("\n", "\\n")
        parts.append(f'{key}="{val}"')
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def _fmt(v: float) -> str:
    if v != v:                      # NaN
        return "NaN"
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    if isinstance(v, float) and v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


def render() -> str:
    """The whole live metric tree in text exposition format."""
    try:
        # loongledger gauges mirror on the self-monitor cadence; a scrape
        # refreshes them too (cheap, idempotent) so the conservation
        # series is live from the first scrape, not the first cadence
        from . import ledger as _ledger
        _ledger.export_refresh()
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongslo freshness/burn gauges mirror the same way: a scrape is
        # never staler than one render
        from . import slo as _slo
        _slo.export_refresh()
    except Exception:  # noqa: BLE001
        pass
    try:
        # the file server's plain counters mirror the same way
        from . import runtime_stats as _rs
        _rs.refresh_file_input()
    except Exception:  # noqa: BLE001
        pass
    by_name: Dict[Tuple[str, str], List[str]] = {}

    def emit(name: str, typ: str, line: str) -> None:
        by_name.setdefault((name, typ), []).append(line)

    for rec in WriteMetrics.instance().records():
        labels = dict(rec.labels)
        labels["category"] = rec.category
        # one snapshot per record: it already carries the histogram
        # percentiles, so only the bucket vectors need a separate read
        snap = rec.snapshot(reset_counters=False)
        for raw, value in snap["counters"].items():
            name = _metric_name(raw)
            emit(name, "counter", f"{name}{_label_str(labels)} {_fmt(value)}")
        for raw, value in snap["gauges"].items():
            name = _metric_name(raw)
            emit(name, "gauge", f"{name}{_label_str(labels)} {_fmt(value)}")
        for hist in rec.histograms():
            name = _metric_name(hist.name)
            hsnap = snap["histograms"].get(hist.name)
            if hsnap is None:      # registered after the snapshot above
                continue
            for le, cum in hist.buckets():
                le_label = 'le="%s"' % _fmt(le)
                emit(name, "histogram",
                     f"{name}_bucket{_label_str(labels, le_label)} {cum}")
            emit(name, "histogram",
                 f"{name}_sum{_label_str(labels)} {_fmt(hsnap['sum'])}")
            emit(name, "histogram",
                 f"{name}_count{_label_str(labels)} {hsnap['count']}")
            for q in ("p50", "p90", "p99"):
                qname = f"{name}_{q}"
                emit(qname, "gauge",
                     f"{qname}{_label_str(labels)} {_fmt(hsnap[q])}")
    out: List[str] = []
    for (name, typ) in sorted(by_name):
        out.append(f"# TYPE {name} {typ}")
        # insertion order, not lexical: histogram buckets must stay in
        # ascending `le` order ("+Inf" sorts lexically first)
        out.extend(by_name[(name, typ)])
    return "\n".join(out) + "\n"


def process_workers() -> int:
    """Active processor shard count, 0 when no runner is live."""
    from ..runner import processor_runner as _pr
    runner = _pr._active_runner
    return runner.thread_count if runner is not None else 0


_TASK_DIR = "/proc/self/task"


def _read_proc(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def _task_account(tid: int) -> dict:
    """The kernel's account of one task of this process, from the three
    files of ``/proc/self/task/<tid>``.  A field the kernel does not give
    (no schedstat, or one a sandbox fills with zeros) is absent, never 0;
    without schedstat ``cpu_s`` is ``stat``'s ``utime + stime`` (10 ms
    ticks).  Files only: a task that ended meanwhile reads as nothing,
    where asking a dead thread for its CPU clock (``pthread_getcpuclockid``
    on a stale ident) takes the process down."""
    row: dict = {}
    sched = (_read_proc(f"{_TASK_DIR}/{tid}/schedstat") or "").split()
    if len(sched) >= 3 and any(x != "0" for x in sched[:3]):
        # ns on a CPU, ns runnable and waiting for one, timeslices run
        row["cpu_s"] = int(sched[0]) / 1e9
        row["runq_wait_s"] = int(sched[1]) / 1e9
        row["timeslices"] = int(sched[2])
    for line in (_read_proc(f"{_TASK_DIR}/{tid}/status") or "").splitlines():
        if line.startswith("voluntary_ctxt_switches:"):
            row["voluntary_switches"] = int(line.split()[1])
        elif line.startswith("nonvoluntary_ctxt_switches:"):
            row["involuntary_switches"] = int(line.split()[1])
    # the comm field may hold spaces and brackets: count from its end.
    # utime, stime and processor are fields 14, 15 and 39 of the line
    stat = (_read_proc(f"{_TASK_DIR}/{tid}/stat") or "").rpartition(")")[2] \
        .split()
    if len(stat) >= 37:
        row["last_cpu"] = int(stat[36])
        if "cpu_s" not in row:
            row["cpu_s"] = (int(stat[11]) + int(stat[12])) \
                / os.sysconf("SC_CLK_TCK")
    return row


def threads_status() -> dict:
    """/debug/status ``threads``: for each of this process's threads, how
    much of its life was work on a CPU, how much waiting for one, and how
    often it let go of it — the kernel's own counters, read when the page
    is asked for and at no other time (nothing samples between scrapes;
    two scrapes and a subtraction give the shares of the time between).

    ``by_name``: every thread of ``threading.enumerate()`` under its name
    (a second thread of one name is ``<name>#<tid>``).  ``other``: the
    tasks that are no Python thread (the device runtime's and XLA's
    pools), counted and summed."""
    doc: dict = {"at_s": time.monotonic() - _process_t0}
    by_name: dict = {}
    mine = set()
    for th in threading.enumerate():
        tid = th.native_id
        if tid is None:
            continue
        mine.add(tid)
        row = {"tid": tid}
        row.update(_task_account(tid))
        by_name[th.name if th.name not in by_name
                else f"{th.name}#{tid}"] = row
    doc["by_name"] = by_name
    try:
        tids = [int(t) for t in os.listdir(_TASK_DIR) if t.isdigit()]
    except OSError:
        tids = []
    other: dict = {"threads": 0}
    for tid in tids:
        if tid in mine:
            continue
        row = _task_account(tid)
        other["threads"] += 1
        for key in ("cpu_s", "runq_wait_s"):
            if key in row:
                other[key] = other.get(key, 0.0) + row[key]
    if tids:
        doc["other"] = other
    return doc


def collect_status() -> dict:
    """The /debug/status document: a one-page answer to "what is this
    agent doing right now", assembled from observe-only handles.  Every
    section is fail-soft — a half-constructed subsystem (agent starting
    up, test harness) yields an absent section, never a 500."""
    doc: dict = {"time": int(time.time()),
                 "uptime_s": round(time.monotonic() - _process_t0, 1),
                 "pid": os.getpid()}
    try:
        from ..pipeline import pipeline_manager as _pm
        mgr = _pm._active_manager
        if mgr is not None:
            pqm = mgr.process_queue_manager
            with mgr._lock:
                items = list(mgr._pipelines.items())
            pipelines = {}
            flush = {}
            for name, p in items:
                entry: dict = {"queue_key": p.process_queue_key}
                if pqm is not None:
                    q = pqm.get_queue(p.process_queue_key)
                    if q is not None:
                        entry["queue_depth"] = q.size()
                pipelines[name] = entry
                for f in p.flushers:
                    probe = getattr(f.plugin, "flush_status", None)
                    if probe is not None:
                        flush[f"{name}/{f.plugin_id or f.plugin.name}"] = \
                            probe()
            doc["pipelines"] = pipelines
            if flush:
                # the write-through sinks that flush on a sender thread of
                # their own (flusher/flush_sender.py), by "<pipeline>/<plugin
                # id>": batches handed over, batches the sender wrote,
                # hand-overs that found the FIFO full and the seconds they
                # waited, depth now and at most
                doc["flush"] = flush
            # loongtenant: per-tenant generation / last-reload / device-
            # budget-share rows — the multi-tenant control-plane page
            # (reload latency distributions live in the
            # pipeline_reload_seconds histogram on /metrics)
            doc["tenants"] = mgr.tenants_status()
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongledger: per-pipeline conservation residual + lag watermarks
        # inline in the status page (the full boundary matrix lives at
        # /debug/ledger); absent while the ledger is off
        from . import ledger as _ledger
        led = _ledger.active_ledger()
        if led is not None:
            snap = led.snapshot()
            lags = _ledger.lag_snapshot()
            rows = doc.get("pipelines", {})
            for pname, prow in snap.items():
                if pname in rows:
                    rows[pname]["conservation_residual"] = \
                        _ledger.residual_of(prow)
            for pname, ages in lags.items():
                if pname in rows:
                    rows[pname]["queue_lag_seconds"] = round(
                        max(ages.values(), default=0.0), 3)
            doc["ledger"] = {
                "inflight_live": _ledger.live_inflight(),
                "residuals": _ledger.residuals(snap),
            }
    except Exception:  # noqa: BLE001
        pass
    try:
        from ..runner import processor_runner as _pr
        runner = _pr._active_runner
        if runner is not None:
            doc["workers"] = {
                "count": runner.thread_count,
                "inbox_depths": runner.inbox_depths(),
                "lane_overlap": [round(x, 4)
                                 for x in runner.lane_overlap()],
            }
    except Exception:  # noqa: BLE001
        pass
    try:
        from ..runner import flusher_runner as _fr
        fr = _fr._active_runner
        if fr is not None:
            doc["breakers"] = {br.name: br.state.name
                               for br in fr.breakers().values()}
    except Exception:  # noqa: BLE001
        pass
    try:
        # where this process computes (platform, device_kind, versions —
        # named once the agent brought the backend up), the plane's
        # utilization once it has dispatched, and the Tier-1 routing
        # decision (probe numbers, rows kept on the host per tier, counted
        # fallbacks)
        import sys as _sys
        from ..ops import device_info as _di
        from ..ops.device_plane import DevicePlane
        dev: dict = dict(_di.status() or {})
        plane = DevicePlane._instance    # observe-only: never construct
        if plane is not None:
            u = plane.utilization()
            dev.update({k: (round(v, 6) if isinstance(v, float) else v)
                        for k, v in u.items()})
        # observe-only, like the sections below: importing the engine
        # would import jax in a process that never parsed a row
        _eng = _sys.modules.get("loongcollector_tpu.ops.regex.engine")
        if _eng is not None:
            dev["routing"] = _eng.routing_status()
        if dev:
            doc["device"] = dev
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongstream: ring occupancy, per-geometry padding waste, and the
        # width auto-tuner's chosen floors/deadline — the streaming plane's
        # "why is the device starving / what is padding costing" page
        from ..ops import device_stream as _ds
        ring = _ds._ring          # observe-only: never construct
        if ring is not None:
            tuner = _ds._tuner
            doc["streaming"] = {
                "depth": _ds.stream_depth(),
                "ring": ring.totals(),
                "geometries": ring.stats(),
                "tuner": tuner.chosen() if tuner is not None else None,
            }
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongmesh: chip lanes (breaker state, respill/fault counters,
        # per-chip occupancy and in-flight bytes) + every live sharded
        # kernel's psum telemetry, materialised here — off the hot path —
        # into the mesh_*_total counters.  The "which chip is sick / how
        # is the slice loaded" page.  Observe-only: never constructs the
        # router or a mesh.
        import sys as _sys
        _cl = _sys.modules.get("loongcollector_tpu.ops.chip_lanes")
        _mesh = _sys.modules.get("loongcollector_tpu.parallel.mesh")
        mesh_doc: dict = {}
        if _cl is not None:
            r = _cl.active_router()
            if r is not None and r.lane_count():
                mesh_doc.update(r.status())
        if _mesh is not None:
            ks = _mesh.mesh_status()
            if ks is not None:
                mesh_doc.update(ks)
        runner = None
        try:
            from ..runner import processor_runner as _pr
            runner = _pr._active_runner
        except Exception:  # noqa: BLE001
            pass
        if runner is not None and mesh_doc:
            mesh_doc["worker_chip_map"] = runner.chip_lane_map()
        if mesh_doc:
            doc["mesh"] = mesh_doc
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongfuse: fused-DFA compile stats — states/classes per set,
        # cache hit/miss, per-pattern demotions (the "why is grok slow /
        # did my pattern fall off the device tier" page)
        import sys as _sys
        _fuse = _sys.modules.get("loongcollector_tpu.ops.regex.fuse")
        if _fuse is not None:
            doc["fusion"] = _fuse.fusion_status()
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongresident: per-program fused-dispatch rows (stages,
        # dispatch/demotion counts, geometries, cache hit/miss) — the
        # "is my pipeline really one dispatch per batch" page
        import sys as _sys
        _fp = _sys.modules.get("loongcollector_tpu.ops.fused_pipeline")
        if _fp is not None:
            doc["stage_fusion"] = _fp.stage_fusion_status()
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongstruct: per-processor structural-parse fallback accounting
        # (the "is JSON/CSV parsing quietly per-row again" page) — absent
        # until a parse processor has processed rows
        import sys as _sys
        _pt = _sys.modules.get(
            "loongcollector_tpu.processor.parse_telemetry")
        if _pt is not None:
            parse_doc = _pt.status()
            if parse_doc:
                doc["parse"] = parse_doc
    except Exception:  # noqa: BLE001
        pass
    try:
        # multiline assembly (processor/split_multiline.py): lines in,
        # records out, device against host classification and the fate of
        # held open records, by pipeline — absent until a pipeline with a
        # Multiline block has seen a group
        import sys as _sys
        _ml = _sys.modules.get(
            "loongcollector_tpu.processor.split_multiline")
        if _ml is not None:
            ml_doc = _ml.status()
            if ml_doc:
                doc["multiline"] = ml_doc
    except Exception:  # noqa: BLE001
        pass
    try:
        # processor_grok (processor/grok.py): rows through the stage, rows
        # each member of Match took and where its extract ran, rows that
        # met Python's re, by pipeline — absent until such a pipeline has
        # seen a group
        import sys as _sys
        _gk = _sys.modules.get("loongcollector_tpu.processor.grok")
        if _gk is not None:
            gk_doc = _gk.status()
            if gk_doc:
                doc["grok"] = gk_doc
    except Exception:  # noqa: BLE001
        pass
    try:
        # processor_classify_url_tpu (processor/classify_url.py): rows
        # through the rule list, where they were labelled (the fused
        # program's label stage or the host), rows each rule took, by
        # pipeline — absent until such a pipeline has seen a group
        import sys as _sys
        _cu = _sys.modules.get("loongcollector_tpu.processor.classify_url")
        if _cu is not None:
            cu_doc = _cu.status()
            if cu_doc:
                doc["classify_url"] = cu_doc
    except Exception:  # noqa: BLE001
        pass
    try:
        from ..prof import flight as _flight
        rec = _flight.recorder()
        doc["flight"] = {"events": len(rec),
                         "recorded_total": rec.recorded_total(),
                         "dropped": rec.dropped_total()}
    except Exception:  # noqa: BLE001
        pass
    try:
        from .. import prof as _prof
        p = _prof.active_profiler()
        doc["profiler"] = {"active": p is not None,
                           "samples": p.samples_total() if p else 0}
    except Exception:  # noqa: BLE001
        pass
    try:
        from .. import recovery as _recovery
        rdoc = _recovery.status()
        if rdoc is not None:
            doc["recovery"] = rdoc
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongxprof: device-memory ledger — live/peak bytes per allocation
        # family (ring slots, resident columns, DFA tables, sharded staging,
        # side arenas).  Always-on (plain counters), so the section appears
        # whenever the device plane module has been imported.
        import sys as _sys
        _dp = _sys.modules.get("loongcollector_tpu.ops.device_plane")
        if _dp is not None:
            doc["device_memory"] = _dp.device_memory_status()
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongxprof: per-family jit compile/cache accounting + recompile-
        # storm episodes — absent until the first watched_jit wrapper runs
        import sys as _sys
        _cw = _sys.modules.get("loongcollector_tpu.ops.compile_watch")
        if _cw is not None:
            cdoc = _cw.compile_status()
            if cdoc:
                doc["compile"] = cdoc
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongxprof: device timeline occupancy + per-(program, geometry)
        # dispatch decomposition; absent while LOONG_XPROF is off
        import sys as _sys
        _xp = _sys.modules.get("loongcollector_tpu.ops.xprof")
        if _xp is not None:
            xdoc = _xp.status()
            if xdoc is not None:
                doc["xprof"] = xdoc
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongtrace: span / timeline-event counts and spans the ring had
        # to evict; absent while LOONG_TRACE is off
        from .. import trace as _trace
        tdoc = _trace.status()
        if tdoc is not None:
            doc["trace"] = tdoc
    except Exception:  # noqa: BLE001
        pass
    try:
        # the file server's always-on counters: rounds, governor-stretched
        # sleeps, chunk reads, reads blocked on a full queue; absent in an
        # agent without a file input
        from . import runtime_stats as _rs
        fdoc = _rs.file_input_status()
        if fdoc is not None:
            doc["file_input"] = fdoc
    except Exception:  # noqa: BLE001
        pass
    try:
        # start-up phases, seconds since process start (monitor/startup.py)
        from . import startup as _startup
        sdoc = _startup.status()
        if sdoc is not None:
            doc["startup"] = sdoc
    except Exception:  # noqa: BLE001
        pass
    try:
        # the kernel's account of every thread (CPU seconds, run-queue
        # wait, switches), read now: which thread bounds a pipeline, and
        # whether it is working or waiting
        doc["threads"] = threads_status()
    except Exception:  # noqa: BLE001
        pass
    return doc


#: every section collect_status() can emit — the parity contract the
#: tests hold /debug/status to (a new subsystem page must register here)
STATUS_SECTIONS = (
    "time", "uptime_s", "pid",
    "pipelines", "tenants", "ledger", "workers", "breakers",
    "device", "streaming", "mesh", "fusion", "stage_fusion", "parse",
    "flight", "profiler", "recovery",
    "device_memory", "compile", "xprof",
    "trace", "file_input", "flush", "startup", "multiline", "grok",
    "classify_url", "threads",
)


_INDEX = (b"loongcollector_tpu exposition endpoint\n"
          b"  /metrics       Prometheus text exposition\n"
          b"  /healthz       liveness (uptime + worker count)\n"
          b"  /debug/status  running-status JSON\n"
          b"  /debug/pprof   folded stacks (loongprof)\n"
          b"  /debug/flight  flight-recorder ring JSON\n"
          b"  /debug/ledger  event-conservation ledger JSON (loongledger)\n"
          b"  /debug/slo     freshness-SLO plane JSON (loongslo)\n"
          b"  /debug/timeline  unified host/device Chrome-trace JSON "
          b"(loongxprof)\n")

_PROM_CT = "text/plain; version=0.0.4; charset=utf-8"
_JSON_CT = "application/json; charset=utf-8"
_TEXT_CT = "text/plain; charset=utf-8"


class _Handler(http.server.BaseHTTPRequestHandler):
    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler contract
        path = self.path.split("?", 1)[0]
        try:
            if path == "/metrics":
                self._reply(200, _PROM_CT, render().encode("utf-8"))
            elif path == "/healthz":
                doc = {"status": "ok", "pid": os.getpid(),
                       "uptime_s": round(time.monotonic() - _process_t0, 1),
                       "process_workers": process_workers()}
                self._reply(200, _JSON_CT,
                            (json.dumps(doc, sort_keys=True) + "\n").encode())
            elif path == "/debug/status":
                self._reply(200, _JSON_CT,
                            (json.dumps(collect_status(), sort_keys=True,
                                        default=str) + "\n").encode())
            elif path == "/debug/flight":
                from ..prof import flight as _flight
                doc = _flight.recorder().snapshot(reason="live")
                self._reply(200, _JSON_CT,
                            (json.dumps(doc, sort_keys=True,
                                        default=str) + "\n").encode())
            elif path == "/debug/ledger":
                from . import ledger as _ledger
                self._reply(200, _JSON_CT,
                            (json.dumps(_ledger.debug_document(),
                                        sort_keys=True,
                                        default=str) + "\n").encode())
            elif path == "/debug/slo":
                from . import slo as _slo
                self._reply(200, _JSON_CT,
                            (json.dumps(_slo.debug_document(),
                                        sort_keys=True,
                                        default=str) + "\n").encode())
            elif path == "/debug/timeline":
                # loongxprof: the unified host/device execution timeline,
                # loadable directly in Perfetto / chrome://tracing
                from ..trace.export import chrome_trace
                self._reply(200, _JSON_CT,
                            (json.dumps(chrome_trace(), sort_keys=True,
                                        default=str) + "\n").encode())
            elif path == "/debug/pprof":
                from .. import prof as _prof
                p = _prof.active_profiler()
                body = (p.folded_text() if p is not None
                        else "# profiler inactive (set LOONG_PROF=1)\n")
                self._reply(200, _TEXT_CT, body.encode("utf-8"))
            elif path == "/":
                # an index, NOT the metrics page: unknown or bare paths
                # must never masquerade as a scrape target
                self._reply(200, _TEXT_CT, _INDEX)
            else:
                self.send_response(404)
                self.end_headers()
        except Exception as e:  # noqa: BLE001 — a bad record must not 500-loop
            log.exception("exposition render failed")
            self.send_response(500)
            self.end_headers()
            self.wfile.write(repr(e).encode())

    def _reply(self, code: int, ctype: str, body: bytes) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *args):  # scrape traffic is not agent log news
        pass


class ExpositionServer:
    """Lifecycle wrapper; `port=0` binds an ephemeral port (tests)."""

    def __init__(self, port: int, host: str = "127.0.0.1"):
        self.host = host
        self.port = port
        self._server: Optional[http.server.ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> bool:
        if self._server is not None:
            return True
        try:
            self._server = http.server.ThreadingHTTPServer(
                (self.host, self.port), _Handler)
        except OSError as e:
            log.error("exposition endpoint bind %s:%d failed: %s",
                      self.host, self.port, e)
            self._server = None
            return False
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="exposition", daemon=True)
        self._thread.start()
        log.info("exposition endpoint on http://%s:%d/metrics",
                 self.host, self.port)
        return True

    def stop(self) -> None:
        server, self._server = self._server, None
        if server is not None:
            server.shutdown()
            server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None


def start_from_env(env=os.environ) -> Optional[ExpositionServer]:
    """LOONG_EXPO_PORT activates the endpoint at application start."""
    raw = env.get(ENV_PORT)
    if not raw:
        return None
    try:
        port = int(raw)
    except ValueError:
        log.error("bad %s=%r; exposition endpoint stays off", ENV_PORT, raw)
        return None
    server = ExpositionServer(port, env.get(ENV_HOST, "127.0.0.1"))
    return server if server.start() else None
