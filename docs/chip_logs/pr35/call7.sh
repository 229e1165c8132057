#!/bin/bash
# call 7: the cells that read a little worse in their one pair of call 3, two more same-seed pairs each
# (regex512.burst40 e2f_p95_ms +5.5 %, json1k_filter.backlog -2.1 %), and one more of regex512.backlog.
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr35/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
$R c7_burst_P1 $P regex512.burst40 2147499701 45 0
$R c7_burst_C1 $C regex512.burst40 2147499701 45 0
$R c7_burst_C2 $C regex512.burst40 2147499702 45 0
$R c7_burst_P2 $P regex512.burst40 2147499702 45 0
$R c7_json_P1 $P json1k_filter.backlog 2147499711 45 0
$R c7_json_C1 $C json1k_filter.backlog 2147499711 45 0
$R c7_json_C2 $C json1k_filter.backlog 2147499712 45 0
$R c7_json_P2 $P json1k_filter.backlog 2147499712 45 0
$R c7_regex_C1 $C regex512.backlog 2147499721 45 0
$R c7_regex_P1 $P regex512.backlog 2147499721 45 0
