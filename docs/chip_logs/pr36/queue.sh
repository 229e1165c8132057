#!/bin/bash
# queue.sh <timeout> <script>... : run each call script on the chip in turn, asking again while no machine
# is free (a call that held no machine costs nothing) or another call of this repository is in flight; logs
# under /root/scratch/<script name>.log.  The repo is copied when a machine is granted: both sides run from
# the snapshots under .chip_tmp/.
t=$1; shift
for s in "$@"; do
  log=/root/scratch/$(basename $s .sh).log
  for i in $(seq 1 300); do
    if chiprun --status 2>/dev/null | grep -q '"in_flight": 1'; then sleep 15; continue; fi
    chiprun --chips 1 --timeout $t -- bash $s > $log 2>&1
    if ! grep -q "status=transient" $log; then echo "$(date +%H:%M:%S) $s ran at attempt $i" >> /root/scratch/queue.attempts; break; fi
    echo "$(date +%H:%M:%S) $s attempt $i: no machine" >> /root/scratch/queue.attempts
    sleep 15
  done
done
echo "$(date +%H:%M:%S) queue done" >> /root/scratch/queue.attempts
