#!/bin/bash
# call A: the fused-set scan kernel alone (ROADMAP A3); the parent (c674cf0 with this PR's benchmark files
# laid over it) in the new cell, first of all; then Step 0: six untraced 45 s runs a side on six seeds not
# used while developing, sides alternating; a traced run a side; the three controls.
cd /root/repo
mkdir -p chiprun_out
R=.chip_tmp/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
$R cA_grok_P_0 $P grok_nginx.backlog 2147497100 45 0
if ! tail -n 1 chiprun_out/cA_grok_P_0.out | grep -q '"correct"'; then
  echo "the parent gave no result line in the new cell: stopping here"; tail -n 30 chiprun_out/cA_grok_P_0.err; exit 7
fi
timeout 300 python3 .chip_tmp/scan_probe.py 2>&1 | grep -v "^W0\|^I0\|^E0" | tee chiprun_out/cA_scan.log
for k in 1 2 3 4 5 6; do
  if [ $((k % 2)) = 1 ]; then
    $R cA_grok_P_$k $P grok_nginx.backlog 214749720$k 45 0
    $R cA_grok_C_$k $C grok_nginx.backlog 214749720$k 45 0
  else
    $R cA_grok_C_$k $C grok_nginx.backlog 214749720$k 45 0
    $R cA_grok_P_$k $P grok_nginx.backlog 214749720$k 45 0
  fi
done
$R cA_grok_C_t ${C}_full grok_nginx.backlog 2147497301 45 1
$R cA_grok_P_t ${P}_full grok_nginx.backlog 2147497301 45 1
$R cA_grok_C_drop $C grok_nginx.backlog 2147497311 20 0 --fault drop_row
$R cA_grok_C_swap $C grok_nginx.backlog 2147497312 20 0 --fault swap_rows
$R cA_grok_C_dup $C grok_nginx.backlog 2147497313 20 0 --fault dup_row
