#!/bin/sh
# PR 36, call 3: (1) the parent under this PR's benchmark files (chip_scratch/overlay = parent + BENCHMARK.json,
# tpubench/, tests/tpubench/ of the change): the new readers read nothing there and raise nothing;
# (2) ten alternating pairs of --trace 0 on serve-345m-offline-decode; (3) one pair a cell elsewhere.
mkdir -p chiprun_out/pr36c; python3 chip_scratch/pr36_probe.py > chiprun_out/pr36c/probe.txt 2>&1
rm -rf chip_scratch/overlay; cp -r chip_scratch/parent chip_scratch/overlay
cp BENCHMARK.json chip_scratch/overlay/; rm -rf chip_scratch/overlay/tpubench chip_scratch/overlay/tests/tpubench
cp -r tpubench chip_scratch/overlay/tpubench; cp -r tests/tpubench chip_scratch/overlay/tests/tpubench
R="sh chip_scratch/pr36_run.sh"; P=chip_scratch/parent; G=serve-345m-offline-decode
$R pr36c overlay chip_scratch/overlay $G 2147500100 1 overlay chip_scratch/overlay train-345m-1chip 2147500099 1
$R pr36c parent $P $G 2147500101 0 change . $G 2147500101 0  change . $G 2147500102 0 parent $P $G 2147500102 0 \
  parent $P $G 2147500103 0 change . $G 2147500103 0  change . $G 2147500104 0 parent $P $G 2147500104 0 \
  parent $P $G 2147500105 0 change . $G 2147500105 0  change . $G 2147500106 0 parent $P $G 2147500106 0 \
  parent $P $G 2147500107 0 change . $G 2147500107 0  change . $G 2147500108 0 parent $P $G 2147500108 0 \
  parent $P $G 2147500109 0 change . $G 2147500109 0  change . $G 2147500110 0 parent $P $G 2147500110 0
for c in serve-glm47f-offline-decode serve-longcat-offline-decode serve-lfm2-offline-decode train-345m-1chip; do
  $R pr36c parent $P $c 2147500121 0 change . $c 2147500121 0
done
du -sh chiprun_out/pr36c
