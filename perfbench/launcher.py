#!/usr/bin/env python3
"""launcher.py — the agent's own ``main`` with the benchmark's probes around it.

The benchmark's parent never imports jax (one process holds the chip), yet
two readings exist only inside the process that holds it: the device's peak
memory as JAX reports it, and a ``jax.profiler`` trace.  So the benchmark
starts the agent through this file instead of ``python -m loongcollector_tpu``:

    python perfbench/launcher.py <run_dir> --config <dir> --data-dir <dir>

runs ``loongcollector_tpu.application.main`` with the arguments after
``<run_dir>`` — the same entry, in the main thread, signal handlers and all —
and beside it one control thread that watches ``<run_dir>`` for requests:

    trace.start   start a jax.profiler trace into <run_dir>/xplane, clear the
                  span store, emit an alignment mark; answer ``trace.started``
    trace.stop    stop the trace, write the program's finished spans to
                  <run_dir>/spans.jsonl; answer ``trace.stopped``

While a trace runs the thread drains the span store every two seconds (the
store is capped at 50,000 spans; a saturated window makes more).  When
``main`` returns it writes ``<run_dir>/device.json``: platform, kind, count,
and ``peak_bytes_in_use`` of the fullest device.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

POLL_S = 0.02
DRAIN_EVERY_S = 2.0
MARK = "perfbench_mark"


def _answer(run_dir: str, name: str, doc: dict) -> None:
    tmp = os.path.join(run_dir, name + ".tmp")
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, os.path.join(run_dir, name))


def _drain_spans(out) -> int:
    """Move the program's finished spans into ``out`` (one JSON list per
    line: name, start on the perf_counter clock, seconds, id, parent id,
    attributes); ``out`` None drops them."""
    from loongcollector_tpu import trace
    tracer = trace.active_tracer()
    if tracer is None:
        return 0
    spans, _events = tracer.drain()
    for s in spans if out is not None else ():
        out.write(json.dumps([s.name, s._start_perf, s.duration_s or 0.0,
                              s.span_id, s.parent_id, s.attrs],
                             default=str) + "\n")
    return len(spans)


def _control(run_dir: str, stop: threading.Event) -> None:
    start_req = os.path.join(run_dir, "trace.start")
    stop_req = os.path.join(run_dir, "trace.stop")
    tracing = False
    spans_out = None
    last_drain = 0.0
    n_spans = 0
    while not stop.is_set():
        if not tracing and os.path.exists(start_req):
            import jax
            os.remove(start_req)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            spans_out = open(os.path.join(run_dir, "spans.jsonl"), "w")
            _drain_spans(None)                      # before the slice: dropped
            jax.profiler.start_trace(os.path.join(run_dir, "xplane"),
                                     profiler_options=opts)
            with jax.profiler.TraceAnnotation(MARK):
                mark_perf_ns = time.perf_counter_ns()
                mark_unix_ns = time.time_ns()
            tracing, last_drain, n_spans = True, time.monotonic(), 0
            _answer(run_dir, "trace.started",
                    {"mark_perf_ns": mark_perf_ns, "mark_unix_ns": mark_unix_ns})
        elif tracing and os.path.exists(stop_req):
            import jax
            os.remove(stop_req)
            t_stop = time.perf_counter_ns()
            jax.profiler.stop_trace()
            n_spans += _drain_spans(spans_out)
            spans_out.close()
            tracing = False
            _answer(run_dir, "trace.stopped",
                    {"stop_perf_ns": t_stop, "spans": n_spans})
        elif tracing and time.monotonic() - last_drain > DRAIN_EVERY_S:
            n_spans += _drain_spans(spans_out)
            last_drain = time.monotonic()
        stop.wait(POLL_S)
    if tracing:                      # the agent is going down mid-trace
        import jax
        jax.profiler.stop_trace()
        spans_out.close()


def _device_doc() -> dict:
    import jax
    devices = jax.devices()
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def main(argv) -> int:
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    run_dir, agent_args = argv[1], argv[2:]
    # the checkout root (this file's grandparent) holds the program's package
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from loongcollector_tpu.application import main as agent_main
    stop = threading.Event()
    ctl = threading.Thread(target=_control, args=(run_dir, stop),
                           name="perfbench-control", daemon=True)
    ctl.start()
    try:
        rc = agent_main(agent_args)
    finally:
        stop.set()
        ctl.join(timeout=30)
    if rc == 0:
        _answer(run_dir, "device.json", _device_doc())
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv))
