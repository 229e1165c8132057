#!/bin/bash
# call 2: the new cell's other four seeds (six untraced in all with call 1's), the parent with the files laid
# over it beside two of them (same seed), then same-seed pairs of the accepted cells, parent against change,
# sides alternating: four of regex512.backlog, two of each other cell.
cd /root/repo
R=.chip_tmp/run_one.sh
C=/root/repo
P=/root/repo/.chip_tmp/parent
O=/root/repo/.chip_tmp/overlaid
W=multiline_java.backlog
$R c2_ml_C_3 $C $W 2147494203 45 0
$R c2_ml_O_3 $O $W 2147494203 45 0
$R c2_ml_O_4 $O $W 2147494204 45 0
$R c2_ml_C_4 $C $W 2147494204 45 0
$R c2_ml_C_5 $C $W 2147494205 45 0
$R c2_ml_C_6 $C $W 2147494206 45 0
$R c2_regex_P_1 $P regex512.backlog 2147494211 45 0
$R c2_regex_C_1 $C regex512.backlog 2147494211 45 0
$R c2_regex_C_2 $C regex512.backlog 2147494212 45 0
$R c2_regex_P_2 $P regex512.backlog 2147494212 45 0
$R c2_filter_P_1 $P filter512.backlog 2147494221 45 0
$R c2_filter_C_1 $C filter512.backlog 2147494221 45 0
$R c2_json_C_1 $C json1k_filter.backlog 2147494231 45 0
$R c2_json_P_1 $P json1k_filter.backlog 2147494231 45 0
$R c2_burst_P_1 $P regex512.burst40 2147494241 45 0
$R c2_burst_C_1 $C regex512.burst40 2147494241 45 0
$R c2_regex_P_3 $P regex512.backlog 2147494213 45 0
$R c2_regex_C_3 $C regex512.backlog 2147494213 45 0
$R c2_regex_C_4 $C regex512.backlog 2147494214 45 0
$R c2_regex_P_4 $P regex512.backlog 2147494214 45 0
$R c2_filter_C_2 $C filter512.backlog 2147494222 45 0
$R c2_filter_P_2 $P filter512.backlog 2147494222 45 0
$R c2_json_P_2 $P json1k_filter.backlog 2147494232 45 0
$R c2_json_C_2 $C json1k_filter.backlog 2147494232 45 0
$R c2_burst_C_2 $C regex512.burst40 2147494242 45 0
$R c2_burst_P_2 $P regex512.burst40 2147494242 45 0
