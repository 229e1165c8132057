#!/bin/bash
# call E: after call D's traced run of the parent read correct false by agent_complaints alone (the profiler's
# events of the parent's 512-step dense scan put the agent's RSS at 2.1-2.3 GB against the 2,048 MB default),
# config.json gained app_config_traced {memory_usage_limit_mb: 16384}, as file_regex_filter_512 and
# file_json_filter_1k have it.  The traced pair again, on a new seed, from the committed files.
cd /root/repo
mkdir -p chiprun_out
R=.chip_tmp/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/proof
W=grok_nginx.backlog
$R cE_P_t $P $W 2147498111 45 1
$R cE_C_t $C $W 2147498111 45 1
$R cE_P_t2 $P $W 2147498112 45 1
grep -a "agent log" -A3 chiprun_out/cE_P_t.out chiprun_out/cE_P_t2.out chiprun_out/cE_C_t.out | cut -c1-300
