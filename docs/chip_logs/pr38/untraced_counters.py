#!/usr/bin/env python3
"""One run of a cell from the checkout in the current directory, as `perfbench/run.py` makes it, with
the counter-sourced per-layer metrics read in an UNTRACED run too (PR 36's script, with PR 38's
routing shares on the list).

The harness reads the per-layer metrics in traced runs only (`perfbench/benchlib/harness.py`: the
section is `per_layer` if traced, else `end_to_end`), and a PR that adds to the benchmark may not
edit it.  The counters of /debug/status `threads` are scraped at both ends of every window all the
same; this wrapper — a chip log's script, not a benchmark file — appends the readers that need no
trace to the untraced run's list.  Nothing in the window changes: the readers run after the agent
has gone.

    cd <checkout> && python3 /root/repo/docs/chip_logs/pr38/untraced_counters.py \\
        --workload http_classify.backlog --seed 2147502101 --seconds 45 --trace 0
"""

import os
import sys

sys.path.insert(0, os.path.join(os.getcwd(), "perfbench"))

import run as bench_run  # noqa: E402  (perfbench/run.py of the checkout)
from benchlib import spec  # noqa: E402

COUNTER_SOURCED = ("worker_cpu_share", "reader_cpu_share", "enqueue_blocked_share",
                   "classify_device_row_share", "device_row_share.sat", "fused_dispatch_share",
                   "pad_row_share", "reader_blocked_share.sat",
                   "reader_blocked_share.tail", "queue_wait_p50_ms.sat", "queue_wait_p50_ms.tail",
                   "agent_cpu_cores", "compiles_in_window.sat", "compiles_in_window.tail")
_metrics_of_cell = spec.metrics_of_cell


def with_counters(bm, cell, section):
    out = _metrics_of_cell(bm, cell, section)
    if section == "end_to_end":
        out = out + [m for m in _metrics_of_cell(bm, cell, "per_layer")
                     if m["name"] in COUNTER_SOURCED]
    return out


spec.metrics_of_cell = with_counters
sys.exit(bench_run.main())
