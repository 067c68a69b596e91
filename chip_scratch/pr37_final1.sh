#!/bin/sh
# PR 37, the final tree as git would commit it (chip_scratch/archive):
# the smoke's flash check, four --trace 0 pairs and one traced pair of
# train-345m-1chip, one pair of serve-345m-offline-decode (which does
# not run the changed file).
set -x
ROOT=$(pwd)
(cd chip_scratch/archive && python $ROOT/chip_scratch/pr37_flash_smoke.py)
ARCHIVE=1 sh chip_scratch/pr37_train.sh 4 2147500401
for side in change parent; do
  dir=$ROOT/chip_scratch/archive; [ $side = parent ] && dir=$ROOT/chip_scratch/parent
  (cd $dir && python3 tpubench/run.py --workload serve-345m-offline-decode --seed 2147500411 --seconds 20 --trace 0 2>/dev/null | tail -n 1 | cut -c1-400)
done
