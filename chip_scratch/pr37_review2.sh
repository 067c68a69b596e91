#!/bin/sh
# PR 37 after the review (one chip): two more --trace 0 pairs of
# train-345m-1chip on the final tree (parent first, then change first),
# every step's wall time copied back: what a run loses against the
# median step is the host's freezes, or it is not.
set -x
ROOT=$(pwd); OUT=$ROOT/chiprun_out/pr37; mkdir -p $OUT
run() { # side dir seed
  (cd $2 && python3 tpubench/run.py --workload train-345m-1chip --seed $3 --seconds 20 --trace 0 \
     > $OUT/train-345m-1chip_$1_$3_t0.log 2> $OUT/train-345m-1chip_$1_$3_t0.err; \
   echo "== $1 seed $3 rc $?"; tail -n 1 $OUT/train-345m-1chip_$1_$3_t0.log | cut -c1-400; \
   cp tpubench_out/train-345m-1chip/seed$3-trace0/steps.jsonl $OUT/steps_$1_$3.jsonl)
}
S=${1:-2147500611}
run parent $ROOT/chip_scratch/parent $S
run change $ROOT/chip_scratch/archive $S
run change $ROOT/chip_scratch/archive $((S + 1))
run parent $ROOT/chip_scratch/parent $((S + 1))
