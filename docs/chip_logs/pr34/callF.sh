#!/bin/bash
# call F: does the slice special case of ProcessorGrok._apply's install pay?  (REVIEW: drop it unless a
# measurement shows it paying in grok.apply.)  C = the tree without it (index arrays always), S = the same tree
# with it (a run of columns moves whole row pieces).  Three same-seed untraced pairs, sides alternating, and a
# traced pair, on one lease.
cd /root/repo
mkdir -p chiprun_out
R=.chip_tmp/run_one.sh
S=/root/repo/.chip_tmp/proof_slice
C=/root/repo/.chip_tmp/proof
W=grok_nginx.backlog
$R cF_C_1 $C $W 2147498201 45 0; $R cF_S_1 $S $W 2147498201 45 0
$R cF_S_2 $S $W 2147498202 45 0; $R cF_C_2 $C $W 2147498202 45 0
$R cF_C_3 $C $W 2147498203 45 0; $R cF_S_3 $S $W 2147498203 45 0
$R cF_S_t $S $W 2147498211 45 1
$R cF_C_t $C $W 2147498211 45 1
