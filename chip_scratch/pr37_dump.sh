#!/bin/sh
# PR 37: why the train step's temporaries grew by 90 MB: XLA's own dump
# of both trees' train step (a fresh compile cache forces the compile).
set -x
ROOT=$(pwd); OUT=$ROOT/chiprun_out/pr37/dump; mkdir -p $OUT
# XLA's dump and the fresh cache, inside the run's own temporary directory
WORK=$(mktemp -d "${TMPDIR:-$ROOT/chiprun_out}/pr37_dump.XXXXXX")
for side in parent change; do
  dir=$ROOT; [ $side = parent ] && dir=$ROOT/chip_scratch/parent
  (cd $dir && JAX_COMPILATION_CACHE_DIR=$WORK/cache_$side XLA_FLAGS="--xla_dump_to=$WORK/dump_$side --xla_dump_hlo_as_text --xla_dump_hlo_module_re=jit_one_step" \
     python3 tpubench/run.py --workload train-345m-1chip --seed 2147500301 --seconds 20 --trace 0 2>&1 | tail -n 1 | cut -c1-400)
  ls -S $WORK/dump_$side | head -30
  for f in $WORK/dump_$side/*one_step*memory* $WORK/dump_$side/*one_step*after_optimizations-buffer-assignment*; do
    [ -f "$f" ] && gzip -c "$f" | head -c 12000000 > $OUT/${side}_$(basename "$f" | tail -c 80).gz
  done
  for f in $WORK/dump_$side/*one_step*after_optimizations.txt; do
    [ -f "$f" ] && gzip -c "$f" | head -c 12000000 > $OUT/${side}_after_opt.txt.gz
  done
done
ls -la $OUT
rm -rf $WORK
