#!/bin/bash
# call 3 (the tree as it then stood: the second form under the CDLL handle; the final tree is the same under PyDLL).  Fourteen runs, because
# call 2 was ended at its nineteenth for the 45 GiB a call may write to the machine's disk (a run writes 2.6 GiB):
# (a) traced same-seed pairs of regex512.backlog and regex512.burst40; (b) three untraced same-seed pairs of
# regex512.backlog; (c) two untraced pairs of regex512.burst40.
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr37/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
W=regex512.backlog
B=regex512.burst40
$R c3_P_t1 $P $W 2147501301 45 1
$R c3_C_t1 $C $W 2147501301 45 1
$R c3_C_bt1 $C $B 2147501302 45 1
$R c3_P_bt1 $P $B 2147501302 45 1
$R c3_C_u1 $C $W 2147501311 45 0
$R c3_P_u1 $P $W 2147501311 45 0
$R c3_P_u2 $P $W 2147501312 45 0
$R c3_C_u2 $C $W 2147501312 45 0
$R c3_C_u3 $C $W 2147501313 45 0
$R c3_P_u3 $P $W 2147501313 45 0
$R c3_P_b1 $P $B 2147501321 45 0
$R c3_C_b1 $C $B 2147501321 45 0
$R c3_C_b2 $C $B 2147501322 45 0
$R c3_P_b2 $P $B 2147501322 45 0
