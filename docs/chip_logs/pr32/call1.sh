#!/bin/bash
# call 1 (no chip was free for a call of the halves alone: four tries, 180 s each): the two halves
# alone on the chip first, then call2.sh's pairs.
cd /root/repo
mkdir -p chiprun_out
python docs/chip_logs/pr32/halves.py > chiprun_out/halves.log 2> chiprun_out/halves.err
echo "== halves rc=$?"
cat chiprun_out/halves.log
tail -n 3 chiprun_out/halves.err
bash .chip_tmp/call2.sh
