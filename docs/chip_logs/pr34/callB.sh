#!/bin/bash
# call B: (1) a traced run of the change from a throw-away copy whose tracer also records each span's native
# thread id and thread CPU time, with the agent's per-thread CPU sampled beside it (the worker's account);
# (2) Step 0 at 512-byte lines: six untraced runs a side, sides alternating; (3) the five accepted cells once
# a side, same seed.
cd /root/repo
mkdir -p chiprun_out
R=.chip_tmp/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
python3 .chip_tmp/thread_sampler.py chiprun_out/cB_diag.threads.jsonl & SP=$!
KEEP_SPANS=/root/repo/chiprun_out/cB_diag.spans.jsonl $R cB_grok_D_t ${C}_diag grok_nginx.backlog 2147497401 45 1
kill $SP
for k in 1 2 3 4 5 6; do
  if [ $((k % 2)) = 1 ]; then
    $R cB_g512_P_$k ${P}_512 grok_nginx.backlog 214749750$k 45 0
    $R cB_g512_C_$k ${C}_512 grok_nginx.backlog 214749750$k 45 0
  else
    $R cB_g512_C_$k ${C}_512 grok_nginx.backlog 214749750$k 45 0
    $R cB_g512_P_$k ${P}_512 grok_nginx.backlog 214749750$k 45 0
  fi
done
$R cB_regex_P $P regex512.backlog 2147497601 45 0
$R cB_regex_C $C regex512.backlog 2147497601 45 0
$R cB_ml_C $C multiline_java.backlog 2147497602 45 0
$R cB_ml_P $P multiline_java.backlog 2147497602 45 0
$R cB_filter_P $P filter512.backlog 2147497603 45 0
$R cB_filter_C $C filter512.backlog 2147497603 45 0
$R cB_json_C $C json1k_filter.backlog 2147497604 45 0
$R cB_json_P $P json1k_filter.backlog 2147497604 45 0
$R cB_burst_P $P regex512.burst40 2147497605 45 0
$R cB_burst_C $C regex512.burst40 2147497605 45 0
