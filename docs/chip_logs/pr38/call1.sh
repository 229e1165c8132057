#!/bin/bash
# call 1 (the working tree as the change; the parent 508dfc3 from `git archive` with this PR's BENCHMARK.json,
# perfbench/ and tests/perfbench/ laid over it): the first look at http_classify.backlog — a traced same-seed
# pair, an untraced same-seed pair, and the two stream controls on the change.  Six runs.
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr38/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo
W=http_classify.backlog
$R c1_C_t1 $C $W 2147502001 45 1
$R c1_P_u1 $P $W 2147502011 45 0
$R c1_C_u1 $C $W 2147502011 45 0
$R c1_P_t1 $P $W 2147502001 45 1
$R c1_C_drop $C $W 2147502021 10 0 --fault drop_row
$R c1_C_swap $C $W 2147502022 10 0 --fault swap_rows
