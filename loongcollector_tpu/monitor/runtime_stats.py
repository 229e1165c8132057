"""Runtime telemetry gauges for the self-monitor pipelines.

Reference analogue: core/monitor/metric_models + the per-runner metric
records the reference refreshes before each self-monitor send.  These
gauges surface the round-5 subsystems — the async device plane's in-flight
budget, the prometheus stream scraper's drop counter, the eBPF connection
table — so operators see device back-pressure and shedding in the same
internal metrics stream as everything else.
"""

from __future__ import annotations

from .metrics import MetricsRecord

_plane_rec = MetricsRecord(category="device_plane",
                           labels={"component": "device_plane"})
_prom_rec = MetricsRecord(category="prometheus_runner",
                          labels={"component": "prometheus"})
_ebpf_rec = MetricsRecord(category="ebpf_connections",
                          labels={"component": "ebpf"})
_mesh_rec = MetricsRecord(category="mesh_parse",
                          labels={"component": "sharded_plane"})
_shard_rec = MetricsRecord(category="processor_shards",
                           labels={"component": "loongshard"})
_prof_rec = MetricsRecord(category="profiler",
                          labels={"component": "loongprof"})
_xprof_rec = MetricsRecord(category="device_xprof",
                           labels={"component": "loongxprof"})
_file_rec = MetricsRecord(category="file_input",
                          labels={"component": "file_server"})
_file_throttle_recs = {
    factor: MetricsRecord(category="file_input",
                          labels={"component": "file_server",
                                  "factor": factor})
    for factor in ("3", "8")}


def file_input_status():
    """The file server's counters (/debug/status ``file_input``); None in
    an agent that never imported the file input or has no file server yet
    (observe-only: imports and constructs nothing)."""
    import sys
    mod = sys.modules.get("loongcollector_tpu.input.file.file_server")
    return mod.status() if mod is not None else None


def refresh_file_input() -> None:
    """The file server's always-on counters into the metrics tree
    (``loong_rounds_total`` … ``loong_rounds_throttled_total{factor=}``):
    lifetime totals set as gauges, so the self-monitor's counter drain
    never resets them.  Called before every snapshot and every scrape."""
    doc = file_input_status()
    if doc is None:
        return
    for name, value in doc.items():
        if name == "rounds_throttled_total":
            for factor, n in value.items():
                _file_throttle_recs[factor].gauge(name).set(n)
        else:
            _file_rec.gauge(name).set(value)


def refresh() -> None:
    """Pull current values into the gauge records (called by the
    self-monitor right before it snapshots).  Every section is fail-soft:
    telemetry must never take down the monitor thread."""
    try:
        refresh_file_input()
    except Exception:  # noqa: BLE001
        pass
    try:
        from ..ops.device_plane import DevicePlane
        plane = DevicePlane._instance   # observe-only: never construct
        if plane is not None:
            _plane_rec.gauge("inflight_bytes").set(plane.inflight_bytes())
            _plane_rec.gauge("budget_bytes").set(plane.budget_bytes)
            _plane_rec.gauge("dispatched_total").set(
                plane.dispatched_total())
            # loongprof utilization accounting: occupancy integral,
            # submit-queue depth, and the "shard more vs device-bound"
            # counter (docs/observability.md)
            u = plane.utilization()
            _plane_rec.gauge("budget_held_fraction_now").set(
                u["held_fraction"])
            _plane_rec.gauge("budget_occupancy_avg").set(u["occupancy_avg"])
            # time with bytes in flight — NOT chip load: it read 0.45 where
            # the device trace read 0.015 busy (PERF.md section 6)
            _plane_rec.gauge("device_inflight_fraction").set(
                u["inflight_fraction"])
            # monotone integrals next to the lifetime averages: rate()
            # over a scrape pair recovers the RECENT fraction, which the
            # averages cannot show on a long-lived agent
            _plane_rec.gauge("budget_occupancy_integral_seconds").set(
                u["occupancy_integral_s"])
            _plane_rec.gauge("device_inflight_seconds").set(u["inflight_s"])
            _plane_rec.gauge("submit_queue_depth").set(
                u["submit_queue_depth"])
            _plane_rec.gauge("device_idle_while_backlogged_ms").set(
                u["idle_while_backlogged_ms"])
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongstream: batch-ring occupancy + padding waste + the auto-
        # tuner's live decisions, next to the plane budget they feed
        # (observe-only: a pipeline that never streamed exports nothing)
        from ..ops import device_stream as _ds
        ring = _ds._ring
        if ring is not None:
            totals = ring.totals()
            _plane_rec.gauge("ring_slots_leased").set(totals["leased"])
            _plane_rec.gauge("ring_slots_pooled").set(totals["pooled"])
            _plane_rec.gauge("batch_padding_fraction_lifetime").set(
                totals["padding_fraction"])
            _plane_rec.gauge("stream_depth").set(_ds.stream_depth())
        tuner = _ds._tuner
        if tuner is not None:
            _plane_rec.gauge("stream_flush_deadline_ms").set(
                tuner.flush_deadline_s() * 1000.0)
    except Exception:  # noqa: BLE001
        pass
    try:
        # psum'd mesh telemetry from the most recent sharded dispatch; the
        # int() materialisation happens HERE (monitor cadence), never on
        # the dispatch hot path
        from ..ops.regex.engine import _engine_cache, _engine_cache_lock
        with _engine_cache_lock:
            engines = list(_engine_cache.values())
        # LRU dict: most-recently-used engines live at the END — walk in
        # reverse so the gauges report the freshest mesh dispatch
        for eng in reversed(engines):
            sharded = getattr(eng, "_sharded", None)
            stats = getattr(sharded, "last_stats", None)
            if stats:
                _mesh_rec.gauge("devices").set(sharded.plane.num_devices)
                _mesh_rec.gauge("last_matched").set(int(stats["matched"]))
                _mesh_rec.gauge("last_events").set(int(stats["events"]))
                _mesh_rec.gauge("last_bytes").set(int(stats["bytes"]))
                # loongmesh: the monitor cadence is an off-hot-path fold
                # point for the queued psum stats (mesh_*_total counters)
                sharded.materialize_stats()
                break
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongmesh chip lanes: breaker states + respill totals in the
        # same stream (observe-only — the per-lane counters/gauges export
        # through each lane's own record; this is the fleet-level rollup)
        from ..ops import chip_lanes as _cl
        r = _cl.active_router()
        if r is not None and r.lane_count():
            _mesh_rec.gauge("chip_lanes").set(r.lane_count())
            _mesh_rec.gauge("chip_lanes_open").set(sum(
                1 for l in r.lanes
                if l.breaker_state().name != "CLOSED"))
            _mesh_rec.gauge("chip_lane_respilled_events").set(
                sum(l.respilled_events() for l in r.lanes))
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongshard: live shard backlog — an imbalanced affinity hash or a
        # wedged worker shows up here as one inbox holding the max depth
        from ..runner import processor_runner as _pr
        runner = _pr._active_runner       # observe-only: never construct
        if runner is not None:
            depths = runner.inbox_depths()
            _shard_rec.gauge("process_workers").set(runner.thread_count)
            _shard_rec.gauge("inbox_backlog_groups").set(sum(depths))
            _shard_rec.gauge("inbox_backlog_max").set(
                max(depths) if depths else 0)
            overlaps = runner.lane_overlap()
            _shard_rec.gauge("lane_overlap_ratio").set(
                sum(overlaps) / len(overlaps) if overlaps else 0.0)
        else:
            # no live runner: zero rather than freeze the last values — a
            # stopped runner must not export a phantom backlog (or a
            # phantom device-overlap signal)
            _shard_rec.gauge("process_workers").set(0)
            _shard_rec.gauge("inbox_backlog_groups").set(0)
            _shard_rec.gauge("inbox_backlog_max").set(0)
            _shard_rec.gauge("lane_overlap_ratio").set(0.0)
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongprof: sampler + flight-ring health in the same stream as
        # everything else (per-scope self_cost_ms counters export through
        # their own records — the profiler owns those)
        from .. import prof as _prof
        from ..prof import flight as _flight
        p = _prof.active_profiler()
        _prof_rec.gauge("prof_active").set(1.0 if p is not None else 0.0)
        _prof_rec.gauge("prof_samples_total").set(
            float(p.samples_total()) if p is not None else 0.0)
        rec = _flight.recorder()
        _prof_rec.gauge("flight_events").set(float(len(rec)))
        _prof_rec.gauge("flight_recorded_total").set(
            float(rec.recorded_total()))
        _prof_rec.gauge("flight_dropped_total").set(
            float(rec.dropped_total()))
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongledger: mirror boundary totals + residual + lag watermarks
        # into per-pipeline gauge records (no-op while the ledger is off)
        from . import ledger
        ledger.export_refresh()
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongslo: mirror freshness/burn-rate gauges on the same cadence
        # (no-op while the SLO plane is off)
        from . import slo
        slo.export_refresh()
    except Exception:  # noqa: BLE001
        pass
    try:
        # loongxprof: device-memory ledger + timeline occupancy + compile
        # accounting rollup (per-family compile counters/histograms export
        # through compile_watch's own shared records — this is the fleet-
        # level "is anything storming / leaking" summary).  Observe-only:
        # sys.modules probes, never an import that constructs a plane.
        import sys as _sys
        _dp = _sys.modules.get("loongcollector_tpu.ops.device_plane")
        if _dp is not None:
            mem = _dp.device_memory_status()
            _xprof_rec.gauge("device_mem_live_bytes_total").set(
                float(mem["total_live_bytes"]))
            for fam, row in mem["families"].items():
                _xprof_rec.gauge(f"device_mem_live_bytes_{fam}").set(
                    float(row["live_bytes"]))
                _xprof_rec.gauge(f"device_mem_peak_bytes_{fam}").set(
                    float(row["peak_bytes"]))
        _cw = _sys.modules.get("loongcollector_tpu.ops.compile_watch")
        if _cw is not None:
            cdoc = _cw.compile_status()
            _xprof_rec.gauge("jit_families").set(float(len(cdoc)))
            _xprof_rec.gauge("jit_storm_episodes_total").set(float(
                sum(row["storm_episodes"] for row in cdoc.values())))
        _xp = _sys.modules.get("loongcollector_tpu.ops.xprof")
        if _xp is not None:
            xdoc = _xp.status()
            _xprof_rec.gauge("xprof_active").set(
                1.0 if xdoc is not None else 0.0)
            if xdoc is not None:
                _xprof_rec.gauge("xprof_dispatches_recorded").set(
                    float(xdoc["dispatches"]))
                _xprof_rec.gauge("xprof_dispatches_closed").set(
                    float(xdoc["closed"]))
                _xprof_rec.gauge("xprof_dispatches_dropped").set(
                    float(xdoc["dropped"]))
    except Exception:  # noqa: BLE001
        pass
    try:
        from ..input.prometheus.scraper import PrometheusInputRunner
        runner = PrometheusInputRunner._instance
        if runner is not None:
            _prom_rec.gauge("dropped_groups").set(runner.dropped_groups)
    except Exception:  # noqa: BLE001
        pass
    try:
        from ..input.ebpf.adapter import EventSource
        from ..input.ebpf.server import EBPFServer
        server = EBPFServer._instance
        if server is not None:
            netobs = server._managers.get(EventSource.NETWORK_OBSERVE)
            if netobs is not None:
                cm = netobs.connections
                _ebpf_rec.gauge("connections").set(cm.connection_count())
                _ebpf_rec.gauge("dropped_connections").set(cm.dropped_conns)
                _ebpf_rec.gauge("unmatched_responses").set(
                    cm.unmatched_responses)
            _ebpf_rec.gauge("process_cache_size").set(
                server.proc_tree.size())
            _ebpf_rec.gauge("process_cache_misses").set(
                server.proc_tree.misses)
    except Exception:  # noqa: BLE001
        pass
