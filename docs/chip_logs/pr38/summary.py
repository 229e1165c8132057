#!/usr/bin/env python3
"""summary.py <prefix>: one digest a run from chiprun_out/<prefix>*.out / .err (what run_one.sh printed on the
chip, made again from the files the call brought back: the tool shows only the end of a call's output)."""
import glob
import json
import re
import statistics
import sys

for out in sorted(glob.glob(f"chiprun_out/{sys.argv[1]}*.out")):
    tag = out.split("/")[-1][:-4]
    lines = open(out).read().strip().splitlines()
    try:
        doc = json.loads(lines[-1])
    except (ValueError, IndexError):
        print(f"== {tag}: no result line")
        continue
    bad = {k: v["value"] for k, v in doc["checks"].items() if v["value"] > v["limit"]}
    print(f"== {tag} correct {doc['correct']} failed {doc['failed']} bad {bad} device "
          f"{ {k: doc['device'].get(k) for k in ('kind', 'memory_peak_bytes', 'busy_s', 'window_s')} }")
    print("   ", json.dumps({k: round(v["value"], 4) for k, v in doc["metrics"].items()}))
    if "breakdown" in doc:
        print("    breakdown", json.dumps(doc["breakdown"]))
    for ln in lines[:-1]:
        if ln.startswith("series delivered"):
            v = [float(x) for x in re.findall(r"[\d.]+", ln.split("[")[1])]
            print(f"    seconds of the window: median {statistics.median(v):.2f} MB/s, under 110: "
                  f"{sum(x < 110 for x in v)}, at 0: {sum(x == 0 for x in v)}")
        if ln.startswith(("series delivered", "routing:", "set-up")):
            print("   ", ln[:600])
    for ln in open(out[:-4] + ".err"):
        if ln.startswith(("the worker's account", "device idle seconds of", "classify_url at", "classify program")):
            print("   ", ln.strip()[:3000])
