#!/bin/sh
# PR 36, after the review (idle_roundtrip_share in place of the launch/wake pair, no slow_step_starved_ms entry,
# schedstat + /proc/pressure/cpu alone): (1) chip_smoke's serve phase; (2) the committed files alone
# (chip_scratch/archive = git archive $(git write-tree)): one --trace 1 run of each one-chip cell; (3) the parent
# 44f785e under this PR's benchmark files (chip_scratch/parent = git archive HEAD + BENCHMARK.json + tpubench/),
# one --trace 1 run: the new readers read nothing and raise nothing.
tag=pr36i; mkdir -p chiprun_out/$tag
python3 chip_scratch/pr36_smoke_serve.py > chiprun_out/$tag/smoke_serve.out 2> chiprun_out/$tag/smoke_serve.err
echo "smoke serve rc=$?"; grep -a "^\[serve\]\|serve phase ok" chiprun_out/$tag/smoke_serve.out | cut -c1-400
A=chip_scratch/archive; P=chip_scratch/parent
sh chip_scratch/pr36_run.sh $tag archive $A serve-345m-offline-decode 2147500071 1 archive $A serve-glm47f-offline-decode 2147500072 1 \
  archive $A serve-longcat-offline-decode 2147500073 1 archive $A serve-lfm2-offline-decode 2147500074 1 \
  archive $A train-345m-1chip 2147500075 1 parentbench $P serve-glm47f-offline-decode 2147500076 1
du -sh chiprun_out/$tag
