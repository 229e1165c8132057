#!/bin/bash
# call 5: call 4's two runs had a stretch of 25 s at 60-90 MB/s. The program's or the machine's? The new cell
# three more times with an accepted cell between them as the machine's control.
cd /root/repo
R=.chip_tmp/run_one.sh
C=/root/repo/.chip_tmp/final
W=multiline_java.backlog
$R c5_ml_C_1 $C $W 2147494501 45 0
$R c5_regex_C_1 $C regex512.backlog 2147494511 45 0
$R c5_ml_C_2 $C $W 2147494502 45 0
$R c5_ml_C_3 $C $W 2147494503 45 0
