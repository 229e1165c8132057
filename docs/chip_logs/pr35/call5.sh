#!/bin/bash
# (PR 36) diag_patch.py, diag_patch2.py and diag_read.py are gone: the tracer now records cpu_s and tid on every span itself
# (loongcollector_tpu/trace/tracer.py; perfbench/benchlib/threads.py reads them), so this script is a record, not a recipe.
# call 5: the final tree after the lean path (no index arrays, no buffers, few numpy calls where every row of a
# group rides; git archive $(git write-tree) under .chip_tmp/change) against the parent (1a0e1c9) in the
# claimed cell: six same-seed pairs of 45 s on six new seeds, sides alternating; traced runs (the committed
# files; a copy that lists the cell on the pinned lists; the parent); the thread-time reading (diag_patch.py +
# diag_patch2.py); the three controls; two more pairs of multiline_java.backlog (call 3's one pair read -3.8 %).
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr35/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
W=grok_nginx.backlog
for k in 1 2 3 4 5 6; do
  if [ $((k % 2)) = 1 ]; then
    $R c5_P_$k $P $W 214749950$k 45 0
    $R c5_C_$k $C $W 214749950$k 45 0
  else
    $R c5_C_$k $C $W 214749950$k 45 0
    $R c5_P_$k $P $W 214749950$k 45 0
  fi
done
$R c5_C_t $C $W 2147499511 45 1
$R c5_P_t $P $W 2147499511 45 1
$R c5_Cfull_t ${C}_full $W 2147499512 45 1
rm -f chiprun_out/c5_diag.spans.jsonl
KEEP_SPANS=/root/repo/chiprun_out/c5_diag.spans.jsonl $R c5_Cdiag_t ${C}_diag2 $W 2147499513 45 1
python3 docs/chip_logs/pr35/diag_read.py chiprun_out/c5_diag.spans.jsonl | tee chiprun_out/c5_diag.account.txt
rm -f chiprun_out/c5_diag.spans.jsonl
$R c5_C_drop $C $W 2147499521 20 0 --fault drop_row
$R c5_C_swap $C $W 2147499522 20 0 --fault swap_rows
$R c5_C_dup $C $W 2147499523 20 0 --fault dup_row
$R c5_ml_P1 $P multiline_java.backlog 2147499531 45 0
$R c5_ml_C1 $C multiline_java.backlog 2147499531 45 0
$R c5_ml_C2 $C multiline_java.backlog 2147499532 45 0
$R c5_ml_P2 $P multiline_java.backlog 2147499532 45 0
