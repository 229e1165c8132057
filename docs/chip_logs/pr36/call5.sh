#!/bin/bash
# call 5: the final tree as git would commit it (git archive $(git write-tree): .chip_tmp/change) against the
# parent (6095b54 with this PR's benchmark files laid over it).  Tracing ON: three same-seed traced pairs of
# regex512.backlog, sides alternating (pipeline.process takes no CPU reading any more, /debug/status trace has
# the clock's cost and step); scripts/trace_overhead.py's enabled figure, twice a side; tracing OFF: two more
# pairs of json1k_filter.backlog (call 4's one pair read -6 %) and one more of grok_nginx.backlog (-1.2 %).
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr36/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
W=regex512.backlog
$R c5_P_t1 $P $W 2147500501 45 1
$R c5_C_t1 $C $W 2147500501 45 1
$R c5_C_t2 $C $W 2147500502 45 1
$R c5_P_t2 $P $W 2147500502 45 1
$R c5_P_t3 $P $W 2147500503 45 1
$R c5_C_t3 $C $W 2147500503 45 1
grep -h "the agent's span store" chiprun_out/c5_C_t1.err | cut -c1-300
for k in 1 2; do
  for side in $P $C; do
    echo "== trace_overhead $side round $k"
    ( cd $side && JAX_PLATFORMS=cpu timeout 600 python3 scripts/trace_overhead_pr36.py 2>&1 | grep "paired rounds\|OK\|FAIL" )
  done
done
$R c5_C_json1 $C json1k_filter.backlog 2147500511 45 0
$R c5_P_json1 $P json1k_filter.backlog 2147500511 45 0
$R c5_P_json2 $P json1k_filter.backlog 2147500512 45 0
$R c5_C_json2 $C json1k_filter.backlog 2147500512 45 0
$R c5_P_grok2 $P grok_nginx.backlog 2147500513 45 0
$R c5_C_grok2 $C grok_nginx.backlog 2147500513 45 0
