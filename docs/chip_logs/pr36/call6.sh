#!/bin/bash
# call 6 (NEVER RAN: no machine was free in 23 attempts over 75 min): json1k_filter.backlog untraced read -6.1, -4.5 and +1.4 % in three same-seed pairs (calls 4 and 5) where
# every other cell read within +-2 %: four more pairs, sides alternating, on the final tree.
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr36/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
W=json1k_filter.backlog
$R c6_P_json1 $P $W 2147500601 45 0
$R c6_C_json1 $C $W 2147500601 45 0
$R c6_C_json2 $C $W 2147500602 45 0
$R c6_P_json2 $P $W 2147500602 45 0
$R c6_P_json3 $P $W 2147500603 45 0
$R c6_C_json3 $C $W 2147500603 45 0
$R c6_C_json4 $C $W 2147500604 45 0
$R c6_P_json4 $P $W 2147500604 45 0
