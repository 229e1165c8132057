#!/bin/bash
# call 3: the final tree from the committed files alone (git archive $(git write-tree)), with the routing probe
# taking the least of five readings. The new cell traced once and untraced three times; an accepted cell traced
# on the parent with this PR's benchmark files laid over it (as the driver lays them); two same-seed pairs of
# regex512.backlog and one run of each other accepted cell on the final tree.
cd /root/repo
R=.chip_tmp/run_one.sh
C=/root/repo/.chip_tmp/final
P=/root/repo/.chip_tmp/parent
O=/root/repo/.chip_tmp/overlaid
W=multiline_java.backlog
$R c3_ml_t_C $C $W 2147494301 45 1
$R c3_ml_C_1 $C $W 2147494302 45 0
$R c3_regex_t_O $O regex512.backlog 2147494311 45 1
$R c3_ml_C_2 $C $W 2147494303 45 0
$R c3_regex_P_1 $P regex512.backlog 2147494312 45 0
$R c3_regex_C_1 $C regex512.backlog 2147494312 45 0
$R c3_regex_C_2 $C regex512.backlog 2147494313 45 0
$R c3_regex_P_2 $P regex512.backlog 2147494313 45 0
$R c3_ml_C_3 $C $W 2147494304 45 0
$R c3_filter_C_1 $C filter512.backlog 2147494321 45 0
$R c3_json_C_1 $C json1k_filter.backlog 2147494331 45 0
$R c3_burst_C_1 $C regex512.burst40 2147494341 45 0
$R c3_ml_C_4 $C $W 2147494305 45 0
