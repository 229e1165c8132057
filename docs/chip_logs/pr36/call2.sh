#!/bin/bash
# call 2: one traced run of each other cell on the change (.chip_tmp/change): the nine new metrics in the four
# other backlog cells, worker_runq_share.tail alone in regex512.burst40, and that no per_layer entry that read a
# number at PR 35 (ledger) now reads nothing.
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr36/run_one.sh
C=/root/repo/.chip_tmp/change
$R c2_C_grok_t $C grok_nginx.backlog 2147500201 45 1
$R c2_C_ml_t $C multiline_java.backlog 2147500202 45 1
$R c2_C_burst_t $C regex512.burst40 2147500203 45 1
$R c2_C_filter_t $C filter512.backlog 2147500204 45 1
$R c2_C_json_t $C json1k_filter.backlog 2147500205 45 1
