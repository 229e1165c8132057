#!/bin/bash
# call 5 (the FINAL tree from the committed files: .chip_tmp/final = git archive $(git write-tree), the second
# form under the PyDLL handle, as call 2's `change`; the parent as before): a traced same-seed pair of
# regex512.backlog, three untraced pairs of it and two untraced pairs of regex512.burst40.  Twelve runs.
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr37/run_one.sh
P=/root/repo/.chip_tmp/parent
F=/root/repo/.chip_tmp/final
W=regex512.backlog
B=regex512.burst40
$R c5_F_u1 $F $W 2147501511 45 0
$R c5_P_u1 $P $W 2147501511 45 0
$R c5_P_u2 $P $W 2147501512 45 0
$R c5_F_u2 $F $W 2147501512 45 0
$R c5_F_u3 $F $W 2147501513 45 0
$R c5_P_u3 $P $W 2147501513 45 0
$R c5_P_b1 $P $B 2147501521 45 0
$R c5_F_b1 $F $B 2147501521 45 0
$R c5_F_b2 $F $B 2147501522 45 0
$R c5_P_b2 $P $B 2147501522 45 0
$R c5_P_t1 $P $W 2147501501 45 1
$R c5_F_t1 $F $W 2147501501 45 1
