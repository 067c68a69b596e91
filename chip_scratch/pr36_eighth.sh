#!/bin/sh
# PR 36, after the benchmark check's refusal (tests/tpubench/test_tpubench_program_spans.py restored, the waits tests in
# their own file): the committed files alone (chip_scratch/archive = git archive $(git write-tree)) and the parent 44f785e
# under this PR's benchmark files (chip_scratch/parent = git archive HEAD + BENCHMARK.json + tpubench/ + tests/tpubench/),
# one --trace 1 run each of a serve cell and the train cell, then one --trace 0 pair.
A=chip_scratch/archive; P=chip_scratch/parent; G=serve-345m-offline-decode
sh chip_scratch/pr36_run.sh pr36k archive $A $G 2147500401 1 parentbench $P $G 2147500401 1 \
  archive $A train-345m-1chip 2147500402 1 parentbench $P train-345m-1chip 2147500402 1 \
  parentbench $P $G 2147500403 0 archive $A $G 2147500403 0
