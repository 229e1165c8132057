#!/bin/bash
# call 2: the final tree as git would commit it (git archive $(git write-tree) under .chip_tmp/change) against
# the parent (1a0e1c9 under .chip_tmp/parent) in the claimed cell: six same-seed pairs of 45 s, sides
# alternating; the change once with an EMPTY compile cache (every program compiled anew: agent_complaints at
# cpu_usage_limit 8.0, setup_s); the three controls.
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr35/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
W=grok_nginx.backlog
for k in 1 2 3 4 5 6; do
  if [ $((k % 2)) = 1 ]; then
    $R c2_P_$k $P $W 214749920$k 45 0
    $R c2_C_$k $C $W 214749920$k 45 0
  else
    $R c2_C_$k $C $W 214749920$k 45 0
    $R c2_P_$k $P $W 214749920$k 45 0
  fi
done
rm -rf /tmp/empty_cache; mkdir -p /tmp/empty_cache
JAX_COMPILATION_CACHE_DIR=/tmp/empty_cache $R c2_C_emptycache $C $W 2147499221 45 0
$R c2_C_drop $C $W 2147499231 20 0 --fault drop_row
$R c2_C_swap $C $W 2147499232 20 0 --fault swap_rows
$R c2_C_dup $C $W 2147499233 20 0 --fault dup_row
