#!/bin/bash
# call 4: the final tree from the committed files alone once more, after the cell left flush_offload_share's
# list (a test pins that entry's cells): the new cell traced and untraced.
cd /root/repo
R=.chip_tmp/run_one.sh
C=/root/repo/.chip_tmp/final
W=multiline_java.backlog
$R c4_ml_t_C $C $W 2147494401 45 1
$R c4_ml_C_1 $C $W 2147494402 45 0
