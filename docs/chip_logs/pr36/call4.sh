#!/bin/bash
# call 4: call 1 read tracing ON at -17 % (parent 108-110 MB/s traced, change 90-92) and the threads section without
# schedstat or switches.  So: what this machine's kernel gives a thread and what time.thread_time() costs there
# (host_clocks.py); three more same-seed traced pairs of regex512.backlog on the change WITHOUT the wasted
# readings (no CPU reading for a span its caller timed, or for a stopwatch); then call 3's untraced pairs.
cd /root/repo
mkdir -p chiprun_out
python3 docs/chip_logs/pr36/host_clocks.py 2>&1 | tee chiprun_out/c4_host_clocks.txt
R=docs/chip_logs/pr36/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
W=regex512.backlog
$R c4_C_t1 $C $W 2147500401 45 1
$R c4_P_t1 $P $W 2147500401 45 1
$R c4_P_t2 $P $W 2147500402 45 1
$R c4_C_t2 $C $W 2147500402 45 1
$R c4_C_t3 $C $W 2147500403 45 1
$R c4_P_t3 $P $W 2147500403 45 1
bash docs/chip_logs/pr36/call3.sh
