"""PR 37, after the review: a copy of a tree's attention_pallas.py in
which `_mask_scores` leaves a tile alone when, by its Python-int
positions, the mask would change nothing (a streamed block wholly
below the diagonal, a key block with no padding). Not shipped unless
it reads faster than masking every tile: chip_scratch/pr37_review1.sh.

    python chip_scratch/pr37_mask_identity.py <tree> <new tree>
"""
import os
import shutil
import sys

src, dst = sys.argv[1:3]
shutil.rmtree(dst, ignore_errors=True)
shutil.copytree(os.path.join(src, "paddle_tpu"),
                os.path.join(dst, "paddle_tpu"))
path = os.path.join(dst, "paddle_tpu/incubate/nn/attention_pallas.py")
text = open(path).read()
old = """    if not causal and kv_len is None:
        return s
"""
new = """    n_k = s.shape[1 - q_axis]
    causal = causal and q0 < k0 + n_k - 1
    if kv_len is not None and k0 + n_k <= kv_len:
        kv_len = None
    if not causal and kv_len is None:
        return s
"""
assert text.count(old) == 1
open(path, "w").write(text.replace(old, new))
