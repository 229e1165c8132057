#!/bin/bash
# first look: the working tree against the parent, regex512.backlog; traced change; overlaid parent traced
cd /root/repo
R=.chip_tmp/run_one.sh
P=/root/repo/.chip_tmp/parent
O=/root/repo/.chip_tmp/overlaid
C=/root/repo
export RUNPY=/root/repo/.chip_tmp/probe_run.py
$R c1_regex_parent_1 $P regex512.backlog 2147493101 45 0
$R c1_regex_change_1 $C regex512.backlog 2147493101 45 0
$R c1_regex_change_2 $C regex512.backlog 2147493102 45 0
$R c1_regex_parent_2 $P regex512.backlog 2147493102 45 0
$R c1_regex_t_change $C regex512.backlog 2147493103 45 1
$R c1_regex_t_overlaid $O regex512.backlog 2147493103 45 1
$R c1_json_change_1 $C json1k_filter.backlog 2147493104 45 0
$R c1_json_parent_1 $P json1k_filter.backlog 2147493104 45 0
