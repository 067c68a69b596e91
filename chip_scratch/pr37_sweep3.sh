#!/bin/sh
# (ran against an EARLIER tree of PR 37: rolled loops, spans, `--set _SPAN_ELEMS` / `_UNROLL_TILES`, `--variants` with cond-a-chunk; kept as the record of PERF.md section 7's "designs on the way", not runnable on the final tree)
# PR 37, third call: one grid step a head where the walk is unrolled.
set -x
mkdir -p chiprun_out/pr37
python benchmarks/attn_bench.py --out chiprun_out/pr37/sweep3_change.json
python benchmarks/attn_bench.py --shapes --pairs "256,256;512,512;128,256" --out chiprun_out/pr37/sweep3_shapes.json
