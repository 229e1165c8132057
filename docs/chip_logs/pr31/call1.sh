#!/bin/bash
# call 1: the new cell. The parent with this PR's benchmark files laid over it, traced (does it run, is it
# correct, does it hang); the change untraced twice and traced once; the controls.
cd /root/repo
R=.chip_tmp/run_one.sh
C=/root/repo
O=/root/repo/.chip_tmp/overlaid
W=multiline_java.backlog
$R c1_ml_t_O $O $W 2147494101 45 1
$R c1_ml_C_1 $C $W 2147494102 45 0
$R c1_ml_t_C $C $W 2147494101 45 1
$R c1_ml_C_2 $C $W 2147494103 45 0
$R c1_ml_drop $C $W 2147494104 15 0 --fault drop_row
$R c1_ml_alter $C $W 2147494105 15 0 --fault alter_field
$R c1_ml_swap $C $W 2147494106 15 0 --fault swap_rows
