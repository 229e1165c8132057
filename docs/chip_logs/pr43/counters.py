#!/usr/bin/env python3
"""One run of a cell from the checkout in the current directory, as `perfbench/run.py` makes it, with
the counter-sourced per-layer metrics read in an UNTRACED run too (the HTTP cell's script, with the
masking share on the list).  The harness reads per-layer metrics in traced runs only; the counters of
/debug/status are scraped at both ends of every window all the same, so this wrapper — a chip log's
script, not a benchmark file — appends the readers that need no trace.  Nothing in the window changes:
the readers run after the agent has gone.

    cd <checkout> && python3 <repo>/docs/chip_logs/pr43/counters.py \\
        --workload desens512.backlog --seed 2147604301 --seconds 45 --trace 0
"""

import os
import sys

sys.path.insert(0, os.path.join(os.getcwd(), "perfbench"))

import run as bench_run  # noqa: E402  (perfbench/run.py of the checkout)
from benchlib import spec  # noqa: E402

COUNTER_SOURCED = ("worker_cpu_share", "reader_cpu_share", "enqueue_blocked_share",
                   "desens_device_row_share", "classify_device_row_share", "device_row_share.sat",
                   "fused_dispatch_share", "pad_row_share", "reader_blocked_share.sat",
                   "queue_wait_p50_ms.sat", "agent_cpu_cores", "compiles_in_window.sat",
                   "ts_native_row_share", "io_arrays_per_dispatch")
_metrics_of_cell = spec.metrics_of_cell


def with_counters(bm, cell, section):
    out = _metrics_of_cell(bm, cell, section)
    if section == "end_to_end":
        out = out + [m for m in _metrics_of_cell(bm, cell, "per_layer")
                     if m["name"] in COUNTER_SOURCED]
    return out


spec.metrics_of_cell = with_counters
sys.exit(bench_run.main())
