#!/bin/sh
python benchmarks/attn_bench.py --pairs "256,256;512,512;128,128;512,256" --out chiprun_out/pr37/sweep5_change.json
sh chip_scratch/pr37_dump.sh
