#!/bin/bash
# snapshots.sh (calls 3 and 4, made while the tree bound `lct_timestamp_column` through the library's CDLL handle:
# what calls 1 and 2 ran as `change_cdll`; the final tree went back to the PyDLL handle, call 2's `change`): the
# two sides of every comparison as directories of the repo that .gitignore lists, made in the sandbox before a
# chip call (the chip's copy has no .git).  parent = bee69e2 from `git archive` with this PR's BENCHMARK.json,
# perfbench/ and tests/perfbench/ laid over it (as the driver lays them); change = `git archive $(git write-tree)`:
# the files git would commit, nothing else.
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
du -sh .chip_tmp
