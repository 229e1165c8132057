#!/usr/bin/env python3
"""One run of a cell from the checkout in the current directory, as `perfbench/run.py` makes it, with
this PR's counter-sourced per-layer metrics read in an UNTRACED run too.

The harness reads the per-layer metrics in traced runs only (`perfbench/benchlib/harness.py`: the
section is `per_layer` if traced, else `end_to_end`), and a PR that adds to the benchmark may not
edit it.  The counters of /debug/status `threads` are scraped at both ends of every window all the
same; this wrapper — a chip log's script, not a benchmark file — appends the readers that need no
trace to the untraced run's list.  Nothing in the window changes: the readers run after the agent
has gone.

    cd <checkout> && python3 /root/repo/docs/chip_logs/pr36/untraced_counters.py \\
        --workload regex512.backlog --seed 2147500101 --seconds 45 --trace 0
"""

import os
import sys

sys.path.insert(0, os.path.join(os.getcwd(), "perfbench"))

import run as bench_run  # noqa: E402  (perfbench/run.py of the checkout)
from benchlib import spec  # noqa: E402

COUNTER_SOURCED = ("worker_cpu_share", "reader_cpu_share", "worker_runq_share.sat",
                   "worker_runq_share.tail", "worker_switches_per_MB",
                   "enqueue_blocked_share")
_metrics_of_cell = spec.metrics_of_cell


def with_counters(bm, cell, section):
    out = _metrics_of_cell(bm, cell, section)
    if section == "end_to_end":
        out = out + [m for m in _metrics_of_cell(bm, cell, "per_layer")
                     if m["name"] in COUNTER_SOURCED]
    return out


spec.metrics_of_cell = with_counters
sys.exit(bench_run.main())
