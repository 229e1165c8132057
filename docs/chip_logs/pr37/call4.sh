#!/bin/bash
# call 4 (the tree of call 3: the final program's CDLL copy): one untraced same-seed pair of each cell that bypasses the call, sides alternating,
# then two more pairs of regex512.backlog.  Twelve runs (the disk limit of a call: call 3's header).
cd /root/repo
mkdir -p chiprun_out
R=docs/chip_logs/pr37/run_one.sh
P=/root/repo/.chip_tmp/parent
C=/root/repo/.chip_tmp/change
$R c4_P_o1 $P filter512.backlog 2147501401 45 0
$R c4_C_o1 $C filter512.backlog 2147501401 45 0
$R c4_C_o2 $C json1k_filter.backlog 2147501402 45 0
$R c4_P_o2 $P json1k_filter.backlog 2147501402 45 0
$R c4_P_o3 $P multiline_java.backlog 2147501403 45 0
$R c4_C_o3 $C multiline_java.backlog 2147501403 45 0
$R c4_C_o4 $C grok_nginx.backlog 2147501404 45 0
$R c4_P_o4 $P grok_nginx.backlog 2147501404 45 0
$R c4_P_u1 $P regex512.backlog 2147501411 45 0
$R c4_C_u1 $C regex512.backlog 2147501411 45 0
$R c4_C_u2 $C regex512.backlog 2147501412 45 0
$R c4_P_u2 $P regex512.backlog 2147501412 45 0
