#!/bin/bash
# call 3: tracing OFF (how the driver measures): same-seed pairs of every cell, parent against change, sides
# alternating; two pairs in regex512.backlog and regex512.burst40 (the two that spread most), one in the others.
# Every run through untraced_counters.py: the five counter-sourced metrics and the threads line of an untraced
# window (the parent prints enqueue_blocked_share alone).
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr36/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
$R c3_P_regex1 $P regex512.backlog 2147500301 45 0
$R c3_C_regex1 $C regex512.backlog 2147500301 45 0
$R c3_C_regex2 $C regex512.backlog 2147500302 45 0
$R c3_P_regex2 $P regex512.backlog 2147500302 45 0
$R c3_P_burst1 $P regex512.burst40 2147500303 45 0
$R c3_C_burst1 $C regex512.burst40 2147500303 45 0
$R c3_C_burst2 $C regex512.burst40 2147500304 45 0
$R c3_P_burst2 $P regex512.burst40 2147500304 45 0
$R c3_P_filter $P filter512.backlog 2147500305 45 0
$R c3_C_filter $C filter512.backlog 2147500305 45 0
$R c3_C_json $C json1k_filter.backlog 2147500306 45 0
$R c3_P_json $P json1k_filter.backlog 2147500306 45 0
$R c3_P_ml $P multiline_java.backlog 2147500307 45 0
$R c3_C_ml $C multiline_java.backlog 2147500307 45 0
$R c3_C_grok $C grok_nginx.backlog 2147500308 45 0
$R c3_P_grok $P grok_nginx.backlog 2147500308 45 0
