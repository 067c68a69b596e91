#!/bin/sh
# PR 37: train-345m-1chip, parent (chip_scratch/parent) against change
# (the tree's root, or chip_scratch/archive with ARCHIVE=1), --trace 0
# pairs in the order change, parent, parent, change, ..., then one traced
# run each; the program's memory gauges at exit (pr37_site).
#   sh chip_scratch/pr37_train.sh <pairs> <first seed>
set -x
PAIRS=${1:-2}; SEED=${2:-2147500201}
ROOT=$(pwd); CHANGE=$ROOT
[ -n "$ARCHIVE" ] && CHANGE=$ROOT/chip_scratch/archive
OUT=$ROOT/chiprun_out/pr37; mkdir -p $OUT
run() { # side dir seed trace
  (cd $2 && PYTHONPATH=$ROOT/chip_scratch/pr37_site python3 tpubench/run.py \
     --workload ${CELL:-train-345m-1chip} --seed $3 --seconds 20 --trace $4 \
     > $OUT/${CELL:-train-345m-1chip}_$1_$3_t$4.log 2> $OUT/${CELL:-train-345m-1chip}_$1_$3_t$4.err; \
   echo "== $1 seed $3 trace $4 rc $?"; tail -n 1 $OUT/${CELL:-train-345m-1chip}_$1_$3_t$4.log | cut -c1-1800; \
   grep "pr37\]" $OUT/${CELL:-train-345m-1chip}_$1_$3_t$4.err | grep "temp_bytes\|total_bytes\|kernels/flash" | head -12)
}
i=0
while [ $i -lt $PAIRS ]; do
  s=$((SEED + i))
  if [ $((i % 2)) -eq 0 ]; then run change $CHANGE $s 0; run parent $ROOT/chip_scratch/parent $s 0
  else run parent $ROOT/chip_scratch/parent $s 0; run change $CHANGE $s 0; fi
  i=$((i + 1))
done
s=$((SEED + PAIRS))
run change $CHANGE $s 1
run parent $ROOT/chip_scratch/parent $s 1
