#!/bin/sh
# PR 36: runs of cells, outputs under chiprun_out/$tag. A line a run:
#   pr36_run.sh tag  side dir cell seed trace  [side dir cell seed trace ...]
# side: a label (parent, change, archive); dir: the tree to run in (., chip_scratch/parent, ...)
tag=$1; shift
mkdir -p chiprun_out/$tag
while [ $# -ge 5 ]; do
  side=$1; dir=$2; cell=$3; seed=$4; trace=$5; shift 5
  o=$PWD/chiprun_out/$tag/$cell-$side-$seed-t$trace
  ( cd $dir && python3 tpubench/run.py --workload $cell --seed $seed --seconds 20 --trace $trace --out $o > $o.out 2> $o.err )
  echo "$cell $side seed $seed trace $trace rc=$? $(tail -n 1 $o.out | cut -c1-3500)"
  grep -a "the two clocks" $o.out
done
date
