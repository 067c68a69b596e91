#!/bin/sh
# PR 37, four chips: train-345m-dp4 on the final tree (chip_scratch/archive)
# against the parent: one --trace 0 pair, then the change traced.
set -x
CELL=train-345m-dp4 ARCHIVE=1 sh chip_scratch/pr37_train.sh 1 2147500501
