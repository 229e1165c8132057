#!/bin/bash
# call D (after the review): the final tree as git would commit it (git archive $(git write-tree) under
# .chip_tmp/proof), no needs_of_program: the parent (c674cf0 with this PR's BENCHMARK.json and perfbench/ laid
# over it) runs the new cell on its synchronous path and is compared like any other.  Six same-seed pairs of
# untraced 45 s runs, sides alternating (P C C P ...), on six new seeds; a traced pair; one control; one accepted
# cell traced on the parent with the benchmark as this PR leaves it.
cd /root/repo
mkdir -p chiprun_out
R=.chip_tmp/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/proof
W=grok_nginx.backlog
$R cD_P_1 $P $W 2147498001 45 0; $R cD_C_1 $C $W 2147498001 45 0
$R cD_C_2 $C $W 2147498002 45 0; $R cD_P_2 $P $W 2147498002 45 0
$R cD_P_3 $P $W 2147498003 45 0; $R cD_C_3 $C $W 2147498003 45 0
$R cD_C_4 $C $W 2147498004 45 0; $R cD_P_4 $P $W 2147498004 45 0
$R cD_P_5 $P $W 2147498005 45 0; $R cD_C_5 $C $W 2147498005 45 0
$R cD_C_6 $C $W 2147498006 45 0; $R cD_P_6 $P $W 2147498006 45 0
$R cD_P_t $P $W 2147498011 45 1
$R cD_C_t $C $W 2147498011 45 1
$R cD_C_drop $C $W 2147498021 20 0 --fault drop_row
$R cD_regex_P_t $P regex512.backlog 2147498031 45 1
