#!/bin/bash
# (PR 36) diag_patch.py, diag_patch2.py and diag_read.py are gone: the tracer now records cpu_s and tid on every span itself
# (loongcollector_tpu/trace/tracer.py; perfbench/benchlib/threads.py reads them), so this script is a record, not a recipe.
# call 4: where the stage spans' self time goes on the list program — one traced run of a throw-away copy
# with thread ids, thread CPU time (diag_patch.py) and finer spans inside the two legs (diag_patch2.py).
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr35/run_one.sh
rm -f chiprun_out/c4_diag.spans.jsonl
KEEP_SPANS=/root/repo/chiprun_out/c4_diag.spans.jsonl $R c4_Cdiag2_t /root/repo/.chip_tmp/change_diag2 grok_nginx.backlog 2147499401 45 1
python3 docs/chip_logs/pr35/diag_read.py chiprun_out/c4_diag.spans.jsonl | tee chiprun_out/c4_diag.account.txt
rm -f chiprun_out/c4_diag.spans.jsonl
