#!/bin/sh
# PR 37 after the review (one chip): the final tree as git would commit
# it (chip_scratch/archive: no scale fold, the whole visible tile
# masked, the dkv index map clamped, 96 MiB of VMEM).
# 1. the three kernels: the tree before the review at the cell's shape
#    (the calibration against sweep4 / sweep5), the final tree at the
#    cell's, D=128, 4096 and 8192 keys, and the final tree with a mask
#    that knows when it is the identity at the two streamed shapes;
# 2. chip_smoke's flash check at six shapes;
# 3. train-345m-1chip: two --trace 0 pairs and one traced pair.
# chip_scratch/before_review was `git archive <the index before the
# review> paddle_tpu` (scale fold, diagonal-only mask, 64 MiB), made for
# this call and not kept; chip_scratch/archive is `git archive
# $(git write-tree)`, chip_scratch/parent the parent commit.
set -x
ROOT=$(pwd); OUT=$ROOT/chiprun_out/pr37r; mkdir -p $OUT
python benchmarks/attn_bench.py --tree chip_scratch/before_review --pairs "256,256" --out $OUT/k_before.json
python benchmarks/attn_bench.py --tree chip_scratch/archive --pairs "256,256" --shape 12,16,1024,64 --shape 6,16,1024,128 --shape 3,16,4096,64 --shape 2,16,8192,64 --out $OUT/k_final.json
python chip_scratch/pr37_mask_identity.py chip_scratch/archive chip_scratch/mask_identity
python benchmarks/attn_bench.py --tree chip_scratch/mask_identity --pairs "256,256" --shape 3,16,4096,64 --shape 2,16,8192,64 --out $OUT/k_identity.json
(cd chip_scratch/archive && python $ROOT/chip_scratch/pr37_flash_smoke.py)
ARCHIVE=1 sh chip_scratch/pr37_train.sh 2 ${1:-2147500601}
