#!/usr/bin/env python3
"""spreads.py <prefix> <cell>: medians and spreads of delivered_MBps / setup_s over the untraced runs
chiprun_out/<prefix>_{P,F}_u*.out of one call (P: the overlaid parent, F: the final tree), the spread as the
driver takes it (the distance between the first and third quartile of statistics.quantiles(n=4) over the
median) and with the farthest run left out, both also as a share of the PARENT's median (ISSUE 38's rule)."""
import glob
import json
import statistics
import sys

prefix, cell = sys.argv[1], sys.argv[2]


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values):
    med = statistics.median(values)
    far = max(values, key=lambda v: abs(v - med))
    rest = list(values)
    rest.remove(far)
    return rest


sides = {}
for side in "PF":
    rows = []
    for out in sorted(glob.glob(f"chiprun_out/{prefix}_{side}_u*.out")):
        lines = open(out).read().strip().splitlines()
        if not lines or cell not in lines[0]:
            continue
        doc = json.loads(lines[-1])
        rows.append((out.split("/")[-1][:-4], doc["correct"],
                     {k: v["value"] for k, v in doc["metrics"].items()}))
    sides[side] = rows
parent_median = statistics.median(m["delivered_MBps"] for _t, _c, m in sides["P"]) if sides["P"] else None
for side, rows in sides.items():
    if not rows:
        continue
    print(f"== {side}: {len(rows)} runs, every one correct: {all(c for _t, c, _m in rows)}")
    for name in ("delivered_MBps", "setup_s"):
        values = [m[name] for _t, _c, m in rows]
        line = (f"   {name}: {[round(v, 4) for v in values]} median {statistics.median(values):.4f} "
                f"spread {100 * spread(values):.2f} %")
        if len(values) > 3:
            t = trimmed(values)
            line += f", the farthest left out {100 * spread(t):.2f} %"
            if name == "delivered_MBps" and parent_median:
                q = statistics.quantiles(t, n=4)
                line += (f" = {q[2] - q[0]:.3f} MB/s = {100 * (q[2] - q[0]) / parent_median:.2f} % of the parent's "
                         f"median {parent_median:.3f}")
        print(line)
    for name in sorted({k for _t, _c, m in rows for k in m} - {"delivered_MBps", "setup_s"}):
        values = [m[name] for _t, _c, m in rows if name in m]
        print(f"   {name}: {[round(v, 4) for v in values]}")
