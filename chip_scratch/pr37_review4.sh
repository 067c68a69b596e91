#!/bin/sh
# PR 37 after the review, four chips: train-345m-dp4 on the final tree
# (chip_scratch/archive) against the parent: one --trace 0 pair, then
# the change traced (flash_roofline, peak_hbm_gib.train, the exposed
# all-reduce); the parent's traced numbers are the ledger's.
set -x
ROOT=$(pwd); OUT=$ROOT/chiprun_out/pr37; mkdir -p $OUT
CELL=train-345m-dp4; SEED=${1:-2147500701}
run() { # side dir seed trace
  (cd $2 && PYTHONPATH=$ROOT/chip_scratch/pr37_site python3 tpubench/run.py \
     --workload $CELL --seed $3 --seconds 20 --trace $4 \
     > $OUT/${CELL}_$1_$3_t$4.log 2> $OUT/${CELL}_$1_$3_t$4.err; \
   echo "== $1 seed $3 trace $4 rc $?"; tail -n 1 $OUT/${CELL}_$1_$3_t$4.log | cut -c1-2400; \
   grep "pr37\]" $OUT/${CELL}_$1_$3_t$4.err | grep "temp_bytes\|total_bytes\|kernels/flash" | head -12)
}
run change $ROOT/chip_scratch/archive $SEED 0
run parent $ROOT/chip_scratch/parent $SEED 0
run change $ROOT/chip_scratch/archive $((SEED + 1)) 1
