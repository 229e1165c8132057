#!/bin/bash
# call 3 (the FINAL tree from the committed files: .chip_tmp/final = git archive $(git write-tree); the parent
# 508dfc3 from `git archive` with this PR's BENCHMARK.json, perfbench/ and tests/perfbench/ laid over it):
# six untraced same-seed pairs of http_classify.backlog (parent, change, change, parent ...), a traced pair,
# the stream controls on the change, one same-seed pair each of filter512.backlog and json1k_filter.backlog
# (the cells that share FusedDispatch and _lockstep_core), and one old cell traced on the overlaid parent.
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr38/run_one.sh
P=/root/repo/.chip_tmp/parent
F=/root/repo/.chip_tmp/final
W=http_classify.backlog
$R h3_P_u1 $P $W 2147502301 45 0
$R h3_F_u1 $F $W 2147502301 45 0
$R h3_F_u2 $F $W 2147502302 45 0
$R h3_P_u2 $P $W 2147502302 45 0
$R h3_P_u3 $P $W 2147502303 45 0
$R h3_F_u3 $F $W 2147502303 45 0
$R h3_F_u4 $F $W 2147502304 45 0
$R h3_P_u4 $P $W 2147502304 45 0
$R h3_P_u5 $P $W 2147502305 45 0
$R h3_F_u5 $F $W 2147502305 45 0
$R h3_F_u6 $F $W 2147502306 45 0
$R h3_P_u6 $P $W 2147502306 45 0
$R h3_F_t1 $F $W 2147502311 45 1
$R h3_P_t1 $P $W 2147502311 45 1
$R h3_F_drop $F $W 2147502321 10 0 --fault drop_row
$R h3_F_swap $F $W 2147502322 10 0 --fault swap_rows
$R h3_F_dup $F $W 2147502323 10 0 --fault dup_row
$R h3_P_f1 $P filter512.backlog 2147502331 45 0
$R h3_F_f1 $F filter512.backlog 2147502331 45 0
$R h3_F_j1 $F json1k_filter.backlog 2147502341 45 0
$R h3_P_j1 $P json1k_filter.backlog 2147502341 45 0
$R h3_P_ft $P filter512.backlog 2147502351 45 1
