#!/usr/bin/env python3
"""summary.py <run.out>: the result line of one run, short — correct, the checks that fail, the device,
the metrics (4 decimals), the breakdown, and the set-up and per-second series lines."""
import json
import sys

try:
    lines = open(sys.argv[1]).read().strip().splitlines()
    doc = json.loads(lines[-1])
    m = {k: round(v["value"], 4) for k, v in doc["metrics"].items()}
    bad = {k: v["value"] for k, v in doc["checks"].items() if v["value"] > v["limit"]}
    print("   correct", doc["correct"], "failed", doc["failed"], "attempted", doc["attempted"],
          "bad", bad, "device", {k: doc["device"].get(k) for k in
                                 ("kind", "memory_peak_bytes", "busy_s", "window_s")})
    print("   ", json.dumps(m))
    if "breakdown" in doc:
        print("    breakdown", json.dumps(doc["breakdown"])[:1500])
    for ln in lines[:-1]:
        if ln.startswith(("series delivered", "routing:", "set-up", "series agent_cpu")):
            print("   ", ln[:400])
except Exception as e:  # noqa: BLE001 — a run without a result line is reported, not fatal
    print("   no result line:", e)
