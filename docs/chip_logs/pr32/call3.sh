#!/bin/bash
# call 3: the final tree after the clean-up, as git would commit it (git archive $(git write-tree) unpacked
# under .chip_tmp/proof), against the parent (7b02c41 with this PR's benchmark files laid over it), same-seed
# pairs with the sides alternating: two more pairs of the claimed cell and a traced run of the change, and a
# second pair of the three cells that had one.
cd /root/repo
R=.chip_tmp/run_one.sh
C=/root/repo/.chip_tmp/proof
P=/root/repo/.chip_tmp/parent
$R c3_regex_C_6 $C regex512.backlog 2147495106 45 0
$R c3_regex_P_6 $P regex512.backlog 2147495106 45 0
$R c3_regex_C_t $C regex512.backlog 2147495109 45 1
$R c3_json_P_2 $P json1k_filter.backlog 2147495142 45 0
$R c3_json_C_2 $C json1k_filter.backlog 2147495142 45 0
$R c3_filter_C_2 $C filter512.backlog 2147495132 45 0
$R c3_filter_P_2 $P filter512.backlog 2147495132 45 0
$R c3_burst_P_2 $P regex512.burst40 2147495152 45 0
$R c3_burst_C_2 $C regex512.burst40 2147495152 45 0
$R c3_regex_P_7 $P regex512.backlog 2147495107 45 0
$R c3_regex_C_7 $C regex512.backlog 2147495107 45 0
$R c3_ml_C_t $C multiline_java.backlog 2147495129 45 1
