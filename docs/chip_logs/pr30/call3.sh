#!/bin/bash
# diagnostics: per-span thread CPU beside wall (traced), and the switch interval at 0.5 ms (diag only)
cd /root/repo
R=.chip_tmp/run_one.sh
export DIAG_OUT=/root/repo/chiprun_out
export RUNPY=/root/repo/.chip_tmp/probe_run.py
$R c3_diagP_t /root/repo/.chip_tmp/diagP regex512.backlog 2147493301 45 1
$R c3_diagA_t /root/repo/.chip_tmp/diagA regex512.backlog 2147493301 45 1
$R c3_P /root/repo/.chip_tmp/parent regex512.backlog 2147493302 45 0
$R c3_siP /root/repo/.chip_tmp/siP regex512.backlog 2147493302 45 0
$R c3_siA /root/repo/.chip_tmp/siA regex512.backlog 2147493302 45 0
$R c3_A /root/repo/.chip_tmp/varA regex512.backlog 2147493302 45 0
ls chiprun_out | grep diag_
