#!/bin/bash
# variants: B = working tree (serialize_view + native append), A = varA (one fused native call, reused buffer)
cd /root/repo
python3 .chip_tmp/handoff.py
R=.chip_tmp/run_one.sh
P=/root/repo/.chip_tmp/parent
A=/root/repo/.chip_tmp/varA
B=/root/repo
export RUNPY=/root/repo/.chip_tmp/probe_run.py
$R c2_regex_P_1 $P regex512.backlog 2147493201 45 0
$R c2_regex_B_1 $B regex512.backlog 2147493201 45 0
$R c2_regex_A_1 $A regex512.backlog 2147493201 45 0
$R c2_regex_A_2 $A regex512.backlog 2147493202 45 0
$R c2_regex_B_2 $B regex512.backlog 2147493202 45 0
$R c2_regex_P_2 $P regex512.backlog 2147493202 45 0
$R c2_regex_B_3 $B regex512.backlog 2147493203 45 0
$R c2_regex_P_3 $P regex512.backlog 2147493203 45 0
$R c2_regex_A_3 $A regex512.backlog 2147493203 45 0
$R c2_regex_t_A $A regex512.backlog 2147493204 45 1
$R c2_regex_t_B $B regex512.backlog 2147493204 45 1
