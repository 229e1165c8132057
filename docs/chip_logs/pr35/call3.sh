#!/bin/bash
# (PR 36) diag_patch.py, diag_patch2.py and diag_read.py are gone: the tracer now records cpu_s and tid on every span itself
# (loongcollector_tpu/trace/tracer.py; perfbench/benchlib/threads.py reads them), so this script is a record, not a recipe.
# call 3: traced runs of the claimed cell — the change from the committed files, the change from a copy whose
# BENCHMARK.json also lists the cell on the pinned lists (full_lists.py: io_arrays_per_dispatch and the extract
# pair), the parent; the thread-time reading of the worker's account from a throw-away copy (diag_patch.py);
# then the five other cells once a side, same seed.
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr35/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
W=grok_nginx.backlog
$R c3_C_t $C $W 2147499301 45 1
$R c3_P_t $P $W 2147499301 45 1
$R c3_Cfull_t ${C}_full $W 2147499302 45 1
rm -f chiprun_out/c3_diag.spans.jsonl
KEEP_SPANS=/root/repo/chiprun_out/c3_diag.spans.jsonl $R c3_Cdiag_t ${C}_diag $W 2147499303 45 1
python3 docs/chip_logs/pr35/diag_read.py chiprun_out/c3_diag.spans.jsonl | tee chiprun_out/c3_diag.account.txt
rm -f chiprun_out/c3_diag.spans.jsonl
$R c3_regex_P $P regex512.backlog 2147499311 45 0
$R c3_regex_C $C regex512.backlog 2147499311 45 0
$R c3_burst_C $C regex512.burst40 2147499312 45 0
$R c3_burst_P $P regex512.burst40 2147499312 45 0
$R c3_filter_P $P filter512.backlog 2147499313 45 0
$R c3_filter_C $C filter512.backlog 2147499313 45 0
$R c3_ml_C $C multiline_java.backlog 2147499314 45 0
$R c3_ml_P $P multiline_java.backlog 2147499314 45 0
$R c3_json_P $P json1k_filter.backlog 2147499315 45 0
$R c3_json_C $C json1k_filter.backlog 2147499315 45 0
