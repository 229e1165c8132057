#!/bin/bash
# the other cells on the same two trees: two same-seed pairs each, sides alternating, and a traced run of the change
cd /root/repo
R=.chip_tmp/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/final
export RUNPY=/root/repo/.chip_tmp/probe_run.py
$R c5_json_C_1 $C json1k_filter.backlog 2147493501 45 0
$R c5_json_P_1 $P json1k_filter.backlog 2147493501 45 0
$R c5_json_P_2 $P json1k_filter.backlog 2147493502 45 0
$R c5_json_C_2 $C json1k_filter.backlog 2147493502 45 0
$R c5_json_t_C $C json1k_filter.backlog 2147493503 45 1
$R c5_burst_P_1 $P regex512.burst40 2147493504 45 0
$R c5_burst_C_1 $C regex512.burst40 2147493504 45 0
$R c5_burst_C_2 $C regex512.burst40 2147493505 45 0
$R c5_burst_P_2 $P regex512.burst40 2147493505 45 0
$R c5_burst_t_C $C regex512.burst40 2147493506 45 1
$R c5_filter_C_1 $C filter512.backlog 2147493507 45 0
$R c5_filter_P_1 $P filter512.backlog 2147493507 45 0
$R c5_filter_P_2 $P filter512.backlog 2147493508 45 0
$R c5_filter_C_2 $C filter512.backlog 2147493508 45 0
$R c5_filter_t_C $C filter512.backlog 2147493509 45 1
