#!/bin/bash
# call 1: what tracing ON costs, and the new readers on both programs.  Three same-seed pairs of traced
# regex512.backlog runs, parent (6095b54 with this PR's benchmark files laid over it: .chip_tmp/parent)
# against change (git archive $(git write-tree): .chip_tmp/change), sides alternating; then
# scripts/trace_overhead.py (this PR's, the same per-group sites on both programs) twice a side.
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr36/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
W=regex512.backlog
$R c1_P_t1 $P $W 2147500101 45 1
$R c1_C_t1 $C $W 2147500101 45 1
$R c1_C_t2 $C $W 2147500102 45 1
$R c1_P_t2 $P $W 2147500102 45 1
$R c1_P_t3 $P $W 2147500103 45 1
$R c1_C_t3 $C $W 2147500103 45 1
for k in 1 2; do
  for side in $P $C; do
    echo "== trace_overhead $side round $k"
    ( cd $side && JAX_PLATFORMS=cpu timeout 600 python3 scripts/trace_overhead_pr36.py 2>&1 | grep "paired rounds\|OK\|FAIL" )
  done
done
