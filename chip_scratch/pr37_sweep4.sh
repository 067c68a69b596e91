#!/bin/sh
# PR 37, fourth call: no loop left in the bodies (every bound a Python
# int; one tile a sub-block and block pair). The parent at the other
# shapes, the whole sweep at the cell's, the other shapes.
# (`--variants`, the same kernels with every visited chunk masked, went
# with the diagonal-only mask after the review: the rows it gave read
# the same times to four digits. Without it the last line runs on the
# final tree.)
set -x
mkdir -p chiprun_out/pr37
python benchmarks/attn_bench.py --tree chip_scratch/parent --shapes --pairs "1024,1024" --out chiprun_out/pr37/sweep4_parent_shapes.json
python benchmarks/attn_bench.py --out chiprun_out/pr37/sweep4_change.json
python benchmarks/attn_bench.py --shapes --variants --pairs "256,256;512,512;128,128" --out chiprun_out/pr37/sweep4_shapes.json
