#!/bin/bash
# call 1: (1) the list program alone on the chip (alone.py: compile wall and CPU, cycle, device time, equal
# to re.fullmatch); (2) the cell as the FIRST process of a fresh checkout of the change (empty compile
# cache: agent_complaints, setup_s, the CPU the compile took); (3) the parent's first process, then two
# more same-seed pairs, sides alternating.  parent = 1a0e1c9 (git archive), change = the working tree as
# git would commit it (git archive $(git write-tree)), both under .chip_tmp/.
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr35/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
W=grok_nginx.backlog
python3 docs/chip_logs/pr35/alone.py --seed 2147499001 > chiprun_out/c1_alone.log 2> chiprun_out/c1_alone.err
echo "== alone rc=$?"; cat chiprun_out/c1_alone.log; tail -n 3 chiprun_out/c1_alone.err | cut -c1-300
$R c1_C_cold $C $W 2147499101 45 0
grep -h "watchdog\|cpu .* > limit" $C/.perfbench_runs/*/agent*.log 2>/dev/null | head -5
$R c1_P_cold $P $W 2147499101 45 0
$R c1_P_2 $P $W 2147499102 45 0
$R c1_C_2 $C $W 2147499102 45 0
$R c1_C_3 $C $W 2147499103 45 0
$R c1_P_3 $P $W 2147499103 45 0
