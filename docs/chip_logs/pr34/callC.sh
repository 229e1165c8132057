#!/bin/bash
# call C: the final tree as git would commit it (git archive $(git write-tree) under .chip_tmp/proof) at
# 512-byte lines.  The parent (c674cf0 with the final benchmark files laid over it) must be refused at once
# in the new cell; two sets of six untraced runs of the cell on twelve new seeds; a traced run (from a copy
# whose BENCHMARK.json also lists the cell on the four pinned lists); the three controls; one accepted cell
# traced on both sides with the benchmark as this PR leaves it.
cd /root/repo
mkdir -p chiprun_out
R=.chip_tmp/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/proof
t0=$(date +%s.%N); ( cd $P && python3 perfbench/run.py --workload grok_nginx.backlog --seed 2147497700 --seconds 45 --trace 0 > /root/repo/chiprun_out/cC_parent_refused.out 2> /root/repo/chiprun_out/cC_parent_refused.err ); rc=$?; t1=$(date +%s.%N)
echo "== parent in the new cell: rc=$rc after $(echo "$t1 - $t0" | bc) s"; tail -n 2 chiprun_out/cC_parent_refused.err | cut -c1-600
for k in 1 2 3 4 5 6; do $R cC_grok_a_$k $C grok_nginx.backlog 214749780$k 45 0; done
$R cC_grok_t ${C}_full grok_nginx.backlog 2147497901 45 1
for k in 1 2 3 4 5 6; do $R cC_grok_b_$k $C grok_nginx.backlog 214749781$k 45 0; done
$R cC_grok_drop $C grok_nginx.backlog 2147497911 20 0 --fault drop_row
$R cC_grok_swap $C grok_nginx.backlog 2147497912 20 0 --fault swap_rows
$R cC_grok_dup $C grok_nginx.backlog 2147497913 20 0 --fault dup_row
$R cC_regex_P_t $P regex512.backlog 2147497921 45 1
$R cC_regex_C_t $C regex512.backlog 2147497921 45 1
