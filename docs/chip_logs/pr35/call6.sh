#!/bin/bash
# call 6: what reading a dispatch's copied-back array costs the host (d2h_reads.py)
cd /root/repo
mkdir -p chiprun_out
python3 docs/chip_logs/pr35/d2h_reads.py 2>/dev/null | tee chiprun_out/c6_d2h_reads.log
