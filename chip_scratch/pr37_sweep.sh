#!/bin/sh
# (ran against an EARLIER tree of PR 37: rolled loops, spans, `--set _SPAN_ELEMS` / `_UNROLL_TILES`, `--variants` with cond-a-chunk; kept as the record of PERF.md section 7's "designs on the way", not runnable on the final tree)
# PR 37, first call: the three flash kernels apart at the cell's shape,
# parent against change, every block pair, then the two variants.
set -x
mkdir -p chiprun_out/pr37
python benchmarks/attn_bench.py --tree chip_scratch/parent --pairs "1024,1024;512,512;256,256" --out chiprun_out/pr37/sweep_parent.json
python benchmarks/attn_bench.py --out chiprun_out/pr37/sweep_change.json
python benchmarks/attn_bench.py --variants --pairs "256,256;512,256;256,512" --out chiprun_out/pr37/sweep_variants.json
