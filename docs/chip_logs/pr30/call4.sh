#!/bin/bash
# the final tree from the committed files alone (git archive $(git write-tree)) against the parent's archive:
# the claimed cell, six same-seed pairs, sides alternating; then traced: the change, and the parent with this
# PR's benchmark files laid over it (as the driver lays them)
cd /root/repo
R=.chip_tmp/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/final
O=/root/repo/.chip_tmp/overlaid
export RUNPY=/root/repo/.chip_tmp/probe_run.py
$R c4_regex_P_1 $P regex512.backlog 2147493401 45 0
$R c4_regex_C_1 $C regex512.backlog 2147493401 45 0
$R c4_regex_C_2 $C regex512.backlog 2147493402 45 0
$R c4_regex_P_2 $P regex512.backlog 2147493402 45 0
$R c4_regex_P_3 $P regex512.backlog 2147493403 45 0
$R c4_regex_C_3 $C regex512.backlog 2147493403 45 0
$R c4_regex_C_4 $C regex512.backlog 2147493404 45 0
$R c4_regex_P_4 $P regex512.backlog 2147493404 45 0
$R c4_regex_P_5 $P regex512.backlog 2147493405 45 0
$R c4_regex_C_5 $C regex512.backlog 2147493405 45 0
$R c4_regex_C_6 $C regex512.backlog 2147493406 45 0
$R c4_regex_P_6 $P regex512.backlog 2147493406 45 0
$R c4_regex_t_C $C regex512.backlog 2147493407 45 1
$R c4_regex_t_O $O regex512.backlog 2147493407 45 1
