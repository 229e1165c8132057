#!/bin/bash
# call 2 (the tree with the prefetch in the native walk; change = git archive $(git write-tree)):
# (a) alone.py again; (b) traced same-seed pairs of regex512.backlog and regex512.burst40; (c) six untraced
# same-seed pairs of regex512.backlog, sides alternating, with the CDLL copy beside three of them;
# (d) two untraced pairs of regex512.burst40; (e) one untraced pair of each cell that bypasses the call.
cd /root/repo
mkdir -p chiprun_out
( cd .chip_tmp/change && python3 docs/chip_logs/pr37/alone.py --rounds 15 2>&1 | grep -v INFO ) | tee chiprun_out/c2_alone.txt
R=docs/chip_logs/pr37/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
D=/root/repo/.chip_tmp/change_cdll
W=regex512.backlog
$R c2_C_t1 $C $W 2147501201 45 1
$R c2_P_t1 $P $W 2147501201 45 1
$R c2_P_bt1 $P regex512.burst40 2147501202 45 1
$R c2_C_bt1 $C regex512.burst40 2147501202 45 1
$R c2_P_u1 $P $W 2147501211 45 0
$R c2_C_u1 $C $W 2147501211 45 0
$R c2_D_u1 $D $W 2147501211 45 0
$R c2_C_u2 $C $W 2147501212 45 0
$R c2_D_u2 $D $W 2147501212 45 0
$R c2_P_u2 $P $W 2147501212 45 0
$R c2_D_u3 $D $W 2147501213 45 0
$R c2_P_u3 $P $W 2147501213 45 0
$R c2_C_u3 $C $W 2147501213 45 0
$R c2_C_u4 $C $W 2147501214 45 0
$R c2_P_u4 $P $W 2147501214 45 0
$R c2_P_u5 $P $W 2147501215 45 0
$R c2_C_u5 $C $W 2147501215 45 0
$R c2_C_u6 $C $W 2147501216 45 0
$R c2_P_u6 $P $W 2147501216 45 0
$R c2_P_b1 $P regex512.burst40 2147501221 45 0
$R c2_C_b1 $C regex512.burst40 2147501221 45 0
$R c2_C_b2 $C regex512.burst40 2147501222 45 0
$R c2_P_b2 $P regex512.burst40 2147501222 45 0
k=0
for cell in filter512.backlog json1k_filter.backlog multiline_java.backlog grok_nginx.backlog; do
  k=$((k+1))
  if [ $((k % 2)) = 1 ]; then
    $R c2_P_o$k $P $cell $((2147501230 + k)) 45 0
    $R c2_C_o$k $C $cell $((2147501230 + k)) 45 0
  else
    $R c2_C_o$k $C $cell $((2147501230 + k)) 45 0
    $R c2_P_o$k $P $cell $((2147501230 + k)) 45 0
  fi
done
