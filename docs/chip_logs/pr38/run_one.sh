#!/bin/bash
# run_one.sh <tag> <dir> <workload> <seed> <seconds> <trace> [extra...]
# One run of perfbench/run.py from the checkout <dir>; an untraced run goes through
# untraced_counters.py (the same run, with the counter-sourced per-layer readers appended).
tag=$1; dir=$2; wl=$3; seed=$4; secs=$5; tr=$6; shift 6
out=/root/repo/chiprun_out
mkdir -p $out
cmd="perfbench/run.py"
[ "$tr" = 0 ] && cmd="/root/repo/docs/chip_logs/pr38/untraced_counters.py"
t0=$(date +%s)
( cd $dir && timeout 900 python3 $cmd --workload $wl --seed $seed --seconds $secs --trace $tr "$@" > $out/$tag.out 2> $out/$tag.err ); rc=$?
t1=$(date +%s)
echo "== $tag rc=$rc wall=$((t1-t0))s"
python3 - "$out/$tag.out" <<'PY'
import json, sys
try:
    lines = open(sys.argv[1]).read().strip().splitlines()
    doc = json.loads(lines[-1])
    m = {k: round(v["value"], 4) for k, v in doc["metrics"].items()}
    bad = {k: v["value"] for k, v in doc["checks"].items() if v["value"] > v["limit"]}
    print("   correct", doc["correct"], "failed", doc["failed"], "bad", bad, "device", {k: doc["device"].get(k) for k in ("kind", "memory_peak_bytes", "busy_s", "window_s")})
    print("   ", json.dumps(m))
    if "breakdown" in doc:
        print("    breakdown", json.dumps(doc["breakdown"])[:1200])
    for ln in lines[:-1]:
        if ln.startswith("series delivered") or ln.startswith("routing:") or ln.startswith("set-up") or ln.startswith("series agent_cpu"):
            print("   ", ln[:400])
except Exception as e:
    print("   no result line:", e)
PY
grep "^classify_url at\|^classify program\|^threads between\|^the worker's account\|^span names whose\|^device idle seconds of\|^input.file.round in" $out/$tag.err | cut -c1-4000 | sed 's/^/   /'
tail -n 3 $out/$tag.err | cut -c1-300 | sed 's/^/   err: /' | grep -v "^   err: check" || true
