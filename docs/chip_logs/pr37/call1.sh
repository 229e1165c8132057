#!/bin/bash
# call 1: (a) the stage alone on the chip's host: numpy column path, the native call through CDLL and
# through PyDLL, alone and beside two threads that want the lock (alone.py; no JAX); (b) the traced
# same-seed pair of regex512.backlog, parent then change; (c) three untraced same-seed triples of
# regex512.backlog — parent, change (PyDLL), change_cdll — sides rotating.
cd /root/repo
mkdir -p chiprun_out
( cd .chip_tmp/change && python3 docs/chip_logs/pr37/alone.py --rounds 15 2>&1 | grep -v INFO ) | tee chiprun_out/c1_alone.txt
R=docs/chip_logs/pr37/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
D=/root/repo/.chip_tmp/change_cdll
W=regex512.backlog
$R c1_P_t1 $P $W 2147501101 45 1
$R c1_C_t1 $C $W 2147501101 45 1
$R c1_P_u1 $P $W 2147501111 45 0
$R c1_C_u1 $C $W 2147501111 45 0
$R c1_D_u1 $D $W 2147501111 45 0
$R c1_D_u2 $D $W 2147501112 45 0
$R c1_C_u2 $C $W 2147501112 45 0
$R c1_P_u2 $P $W 2147501112 45 0
$R c1_C_u3 $C $W 2147501113 45 0
$R c1_P_u3 $P $W 2147501113 45 0
$R c1_D_u3 $D $W 2147501113 45 0
