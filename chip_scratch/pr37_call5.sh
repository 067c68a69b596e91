#!/bin/sh
# PR 37: the sweep of the loop-free kernels, the smoke's flash check at
# six shapes, then the train cell parent against change.
sh chip_scratch/pr37_sweep4.sh
python chip_scratch/pr37_flash_smoke.py
sh chip_scratch/pr37_train.sh 2 2147500201
