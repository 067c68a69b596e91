#!/bin/sh
# (ran against an EARLIER tree of PR 37: rolled loops, spans, `--set _SPAN_ELEMS` / `_UNROLL_TILES`, `--variants` with cond-a-chunk; kept as the record of PERF.md section 7's "designs on the way", not runnable on the final tree)
# PR 37, second call: device times from a trace; loops unrolled block by
# block in wide tiles; statistics as lane-dense rows. Then one traced
# run of the cell at the current defaults.
set -x
mkdir -p chiprun_out/pr37
python benchmarks/attn_bench.py --tree chip_scratch/parent --pairs "1024,1024" --out chiprun_out/pr37/sweep2_parent.json
python benchmarks/attn_bench.py --out chiprun_out/pr37/sweep2_change.json
python benchmarks/attn_bench.py --set _SPAN_ELEMS=0 --pairs "256,256;512,256;512,512;256,512" --out chiprun_out/pr37/sweep2_span1.json
python benchmarks/attn_bench.py --set _UNROLL_TILES=0 --pairs "256,256;512,256;512,512;256,512" --out chiprun_out/pr37/sweep2_rolled.json
python3 tpubench/run.py --workload train-345m-1chip --seed 2147500101 --seconds 20 --trace 1 > chiprun_out/pr37/train1_change_256.json
tail -c 3000 chiprun_out/pr37/train1_change_256.json
