#!/bin/bash
# call G: the final tree as git would commit it (git archive $(git write-tree) under .chip_tmp/proof; the slice
# path of _apply restored after call F).  Six untraced 45 s runs of the new cell on six new seeds, one traced.
cd /root/repo
mkdir -p chiprun_out
R=.chip_tmp/run_one.sh
C=/root/repo/.chip_tmp/proof
W=grok_nginx.backlog
for k in 1 2 3 4 5 6; do $R cG_C_$k $C $W 214749830$k 45 0; done
$R cG_C_t $C $W 2147498311 45 1
