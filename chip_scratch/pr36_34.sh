#!/bin/sh
# PR 36: calls 4 and 3 in one (chips were scarce): the stalls first, then the pairs
sh chip_scratch/pr36_fourth.sh
sh chip_scratch/pr36_third.sh
