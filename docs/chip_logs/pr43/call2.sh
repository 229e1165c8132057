#!/bin/bash
# call 2 — the final tree from the committed files (`git archive $(git write-tree)` in .chip_tmp/final)
# and the parent fe03374 (`git archive`, this PR's BENCHMARK.json, perfbench/ and tests/perfbench/ laid
# over it, in .chip_tmp/parent): the parent on the new cell (it must stop at once with the line source's
# SpecError); six untraced windows and one traced window of desens512.backlog, each its own seed; one
# same-seed pair, parent then change, of filter512.backlog and of http_classify.backlog (they share
# FusedDispatch, fused_pipeline and the device plane's counters with the change).
cd "$(dirname "$0")/../../.."
R=docs/chip_logs/pr43/run_one.sh
P=.chip_tmp/parent
F=.chip_tmp/final
W=desens512.backlog
$R c2_P_desens $P $W 2147604401 45 0
for i in 1 2 3 4 5 6; do
  $R c2_F_u$i $F $W 214760441$i 45 0
done
$R c2_F_t1 $F $W 2147604421 45 1
$R c2_P_filter $P filter512.backlog 2147604431 45 0
$R c2_F_filter $F filter512.backlog 2147604431 45 0
$R c2_P_http $P http_classify.backlog 2147604441 45 0
$R c2_F_http $F http_classify.backlog 2147604441 45 0
python3 docs/chip_logs/pr43/spreads.py c2 $W
