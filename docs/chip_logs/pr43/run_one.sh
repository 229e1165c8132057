#!/bin/bash
# run_one.sh <tag> <checkout> <workload> <seed> <seconds> <trace> [extra...]
# One run of perfbench/run.py from <checkout> (relative to the repo root); an untraced run goes
# through counters.py (the same run, with the counter-sourced per-layer readers appended).
# Output: <tag>.out / .err in the output directory, and a summary on standard output.
root=$(cd "$(dirname "$0")/../../.." && pwd)
tag=$1; dir=$2; wl=$3; seed=$4; secs=$5; tr=$6; shift 6
out=$root/chiprun_out
mkdir -p "$out"
cmd="perfbench/run.py"
[ "$tr" = 0 ] && cmd="$root/docs/chip_logs/pr43/counters.py"
t0=$(date +%s)
( cd "$root/$dir" && timeout 900 python3 "$cmd" --workload "$wl" --seed "$seed" --seconds "$secs" \
    --trace "$tr" "$@" > "$out/$tag.out" 2> "$out/$tag.err" ); rc=$?
t1=$(date +%s)
echo "== $tag rc=$rc wall=$((t1-t0))s"
python3 "$root/docs/chip_logs/pr43/summary.py" "$out/$tag.out"
grep "^desensitize at\|^mask program\|^the worker's account\|^device idle seconds of" "$out/$tag.err" \
    | cut -c1-3000 | sed 's/^/   /'
tail -n 3 "$out/$tag.err" | cut -c1-300 | sed 's/^/   err: /' | grep -v "^   err: check" || true
