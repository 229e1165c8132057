#!/bin/bash
# call 3 — the final tree from the committed files (`git archive $(git write-tree)` in .chip_tmp/final)
# and the parent fe03374 with the new benchmark files laid over it (.chip_tmp/parent): one traced and
# four untraced windows of desens512.backlog on seeds of their own, and one same-seed traced pair,
# parent then change, of filter512.backlog (an old cell run with the benchmark as it now stands).
cd "$(dirname "$0")/../../.."
R=docs/chip_logs/pr43/run_one.sh
P=.chip_tmp/parent
F=.chip_tmp/final
W=desens512.backlog
$R c3_F_t1 $F $W 2147604521 45 1
for i in 1 2 3 4; do
  $R c3_F_u$i $F $W 214760453$i 45 0
done
$R c3_P_filter_t $P filter512.backlog 2147604541 45 1
$R c3_F_filter_t $F filter512.backlog 2147604541 45 1
python3 docs/chip_logs/pr43/spreads.py c3 $W
