#!/usr/bin/env python3
"""spreads.py <prefix> <cell>: median and spread of each end-to-end metric over the untraced runs
<prefix>_F_u*.out of one call (under the output directory), the spread as the benchmark's check
takes it: the distance between the first and the third quartile of statistics.quantiles(n=4), over
the median."""
import glob
import json
import statistics
import sys

prefix, cell = sys.argv[1], sys.argv[2]
runs = []
for out in sorted(glob.glob(f"chiprun_out/{prefix}_F_u*.out")):
    try:
        doc = json.loads(open(out).read().strip().splitlines()[-1])
    except (OSError, ValueError, IndexError):
        continue
    runs.append(doc)
print(f"{cell}: {len(runs)} runs, correct {[d['correct'] for d in runs]}")
for name in ("delivered_MBps", "setup_s"):
    values = [d["metrics"][name]["value"] for d in runs if name in d["metrics"]]
    if len(values) >= 2:
        q = statistics.quantiles(values, n=4)
        print(f"  {name}: median {statistics.median(values)!r} spread "
              f"{(q[2] - q[0]) / statistics.median(values)!r} values {values!r}")
