"""PR 37: chip_smoke's flash check alone, compiled by Mosaic on the chip, at the smoke's own geometry (2 x 16 x 1024 x 64, bf16, default blocks), then at the other shapes the residency rule has to hold for."""
import os, sys
sys.path.insert(0, os.getcwd())
import jax
import chip_smoke

print(jax.devices()[0].device_kind, flush=True)
for b, h, s, d in ((2, 16, 1024, 64), (2, 16, 1024, 128), (1, 4, 2048, 256),
                   (1, 8, 4096, 128), (1, 4, 8192, 64), (2, 4, 1152, 64)):
    g = dict(attn_b=b, heads=h, seq=s, head_dim=d)
    print("flash_attention: ok -", chip_smoke.k_flash(g, False), flush=True)
