#!/bin/sh
# PR 36, after the review, four chips: the committed files alone, one --trace 1 run of train-345m-dp4
sh chip_scratch/pr36_run.sh pr36i archive chip_scratch/archive train-345m-dp4 2147500077 1
