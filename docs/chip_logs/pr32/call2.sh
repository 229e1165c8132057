#!/bin/bash
# call 2: same-seed pairs, parent (7b02c41 with this PR's benchmark files laid over it) against change,
# sides alternating: five untraced pairs of the claimed cell regex512.backlog and a traced pair; two
# untraced pairs and a traced pair of multiline_java.backlog; one untraced pair of each other cell and
# a traced run of the change in filter512.backlog and json1k_filter.backlog.
cd /root/repo
R=.chip_tmp/run_one.sh
C=/root/repo
P=/root/repo/.chip_tmp/parent
$R c2_regex_P_1 $P regex512.backlog 2147495011 45 0
$R c2_regex_C_1 $C regex512.backlog 2147495011 45 0
$R c2_regex_C_2 $C regex512.backlog 2147495012 45 0
$R c2_regex_P_2 $P regex512.backlog 2147495012 45 0
$R c2_regex_P_t $P regex512.backlog 2147495019 45 1
$R c2_regex_C_t $C regex512.backlog 2147495019 45 1
$R c2_ml_C_1 $C multiline_java.backlog 2147495021 45 0
$R c2_ml_P_1 $P multiline_java.backlog 2147495021 45 0
$R c2_ml_P_t $P multiline_java.backlog 2147495029 45 1
$R c2_ml_C_t $C multiline_java.backlog 2147495029 45 1
$R c2_regex_P_3 $P regex512.backlog 2147495013 45 0
$R c2_regex_C_3 $C regex512.backlog 2147495013 45 0
$R c2_filter_C_1 $C filter512.backlog 2147495031 45 0
$R c2_filter_P_1 $P filter512.backlog 2147495031 45 0
$R c2_json_P_1 $P json1k_filter.backlog 2147495041 45 0
$R c2_json_C_1 $C json1k_filter.backlog 2147495041 45 0
$R c2_burst_C_1 $C regex512.burst40 2147495051 45 0
$R c2_burst_P_1 $P regex512.burst40 2147495051 45 0
$R c2_regex_C_4 $C regex512.backlog 2147495014 45 0
$R c2_regex_P_4 $P regex512.backlog 2147495014 45 0
$R c2_ml_P_2 $P multiline_java.backlog 2147495022 45 0
$R c2_ml_C_2 $C multiline_java.backlog 2147495022 45 0
$R c2_filter_C_t $C filter512.backlog 2147495039 45 1
$R c2_json_C_t $C json1k_filter.backlog 2147495049 45 1
$R c2_regex_P_5 $P regex512.backlog 2147495015 45 0
$R c2_regex_C_5 $C regex512.backlog 2147495015 45 0
