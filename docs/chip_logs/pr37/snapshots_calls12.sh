#!/bin/bash
# snapshots_calls12.sh (calls 1 and 2, while the tree bound the call through a PyDLL handle): the two sides of every comparison as directories of the repo that .gitignore lists, made in
# the sandbox before a chip call (the chip's copy has no .git).  parent = bee69e2 from `git archive` with
# this PR's BENCHMARK.json, perfbench/ and tests/perfbench/ laid over it (as the driver lays them);
# change = `git archive $(git write-tree)`: the files git would commit, nothing else; change_cdll = the
# change with `lct_timestamp_column` bound through the library's CDLL handle (lets go of the interpreter
# lock once a group) instead of the PyDLL handle — the one measurement ISSUE 37 asks for, a throw-away copy.
set -e
cd /root/repo
rm -rf .chip_tmp && mkdir -p .chip_tmp/parent .chip_tmp/change
git archive bee69e2036b7553ce97191ead8d4fad3669e5dfb | tar -x -C .chip_tmp/parent
git add -A
git archive $(git write-tree) | tar -x -C .chip_tmp/change
cp .chip_tmp/change/BENCHMARK.json .chip_tmp/parent/BENCHMARK.json
rm -rf .chip_tmp/parent/perfbench .chip_tmp/parent/tests/perfbench
cp -r .chip_tmp/change/perfbench .chip_tmp/parent/perfbench
cp -r .chip_tmp/change/tests/perfbench .chip_tmp/parent/tests/perfbench
cp -r .chip_tmp/change .chip_tmp/change_cdll
python3 - <<'PY'
p = "/root/repo/.chip_tmp/change_cdll/loongcollector_tpu/native.py"
s = open(p).read()
a = "        lib.keeps_lock = _cdll(so_path, ctypes.PyDLL)\n        lib.lct_timestamp_column = lib.keeps_lock.lct_timestamp_column\n"
assert s.count(a) == 1
open(p, "w").write(s.replace(a, ""))
PY
du -sh .chip_tmp
