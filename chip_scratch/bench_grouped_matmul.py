"""PR 35: one launch of the routed experts' grouped matmul at the three expert cells' decode shapes and at the longest prefill
bucket's, by row tile and for both grid designs, in GB/s of the hit experts' weights, beside XLA's `ragged_dot`:
(a) the tile-major grid of jax's megablox `gmm` (tiling (tm, tk, tn)), (b) the repo's group-major, weights-stationary
kernel (`pallas/grouped_matmul.py`, tiles (tm, tn)).  `python chip_scratch/bench_grouped_matmul.py [shape name ...]`"""
import os, sys, time
sys.path.insert(0, os.getcwd())
import numpy as np
import jax, jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
from paddle_tpu.incubate.nn.pallas import grouped_matmul as gm

REPS = 20


def sizes_for(name, rng, e):
    """Group sizes as the cell's routing gives them."""
    if name.startswith("lfm2-decode"):      # 256 tokens x 4 picks over 64 experts
        return np.bincount(rng.integers(0, e, 1024), minlength=e)
    if name.startswith("glm-decode"):       # 64 tokens x 4 picks over 64 experts
        return np.bincount(rng.integers(0, e, 256), minlength=e)
    if name.startswith("longcat-decode"):   # 64 x 12 picks over 768 outputs, 16 held
        picks = rng.integers(0, 768, 768)
        return np.bincount(picks[picks < e], minlength=e)
    return np.bincount(rng.integers(0, e, 8192), minlength=e)   # a 2048-token prefill, 4 picks


# name: (M rows, E groups of the layer, G groups of the stack, layer, K, N)
SHAPES = {
    "lfm2-decode-w13": (1024, 64, 64, 0, 2048, 3072), "lfm2-decode-w2": (1024, 64, 64, 0, 1536, 2048),
    "glm-decode-w13": (256, 64, 384, 3, 2048, 3072), "glm-decode-w2": (256, 64, 384, 3, 1536, 2048),
    "longcat-decode-w13": (768, 16, 64, 2, 6144, 4096), "longcat-decode-w2": (768, 16, 64, 2, 2048, 6144),
    "prefill-w13": (8192, 64, 64, 0, 2048, 3072), "prefill-w2": (8192, 64, 64, 0, 1536, 2048),
}
MINE = {"decode": [(16, None), (32, None), (64, None), (128, None), (None, 512), (None, 1024), (None, 0)],
        "prefill": [(128, 1024), (128, 512), (256, 512), (128, 256)]}
MEGABLOX = {"decode": [(16, 0, 512), (32, 0, 512), (128, 0, 512), (128, 1024, 1024), (256, 0, 512)],
            "prefill": [(128, 0, 512), (256, 0, 512), (512, 0, 512), (256, 1024, 1024)]}


def timed(fn, *args):
    out = fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    out.block_until_ready()
    return out, (time.perf_counter() - t0) / REPS


def run(name):
    m, e, groups, layer, k, n = SHAPES[name]
    kind = "prefill" if name.startswith("prefill") else "decode"
    rng = np.random.default_rng(0)
    sizes = sizes_for(name, rng, e).astype(np.int32)
    full = np.zeros(groups, np.int32)
    full[layer * e:(layer + 1) * e] = sizes
    key = jax.random.PRNGKey(0)
    rows = jax.random.normal(key, (m, k), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (groups, k, n), jnp.bfloat16) * 0.05
    sizes_j, full_j = jnp.asarray(sizes), jnp.asarray(full)
    first = jnp.int32(layer * e)
    held = int(sizes.sum())
    gbytes = int((sizes > 0).sum()) * k * n * 2 / 1e9
    print(f"== {name}: rows {m} (in groups {held}), {int((sizes > 0).sum())} of {e} groups hit (stack {groups}), "
          f"largest {sizes.max()}, K {k} N {n}: {gbytes:.3f} GB of weights", flush=True)

    def report(what, out, dt, ref):
        err = float(jnp.abs(out[:held].astype(jnp.float32) - ref[:held].astype(jnp.float32)).max()) if ref is not None else 0.0
        print(f"{name:20s} {what:34s} {dt * 1e3:8.3f} ms {gbytes / dt:7.1f} GB/s  err {err:.3f}", flush=True)

    ref, dt = timed(jax.jit(jax.lax.ragged_dot), rows, w, full_j)
    report("xla ragged_dot", ref, dt, None)
    own = gm._tiles(m, e, k, n, 2)
    print(f"{name:20s} the rule's tiles: {own}", flush=True)
    for tm, tn in MINE[kind]:
        if kind == "decode":
            if own is None:
                continue
            tm, tn = tm or own[0], (n if tn == 0 else tn or own[1])
        if n % tn or tm > m:
            continue
        fn = jax.jit(lambda r, w, s, f, _t=(tm, tn): gm._call(r, w, s, f, _t, False))
        try:
            out, dt = timed(fn, rows, w, sizes_j, first)
        except Exception as ex:
            print(f"{name:20s} (b) tm {tm} tn {tn}: FAILED {str(ex)[:200]}", flush=True)
            continue
        report(f"(b) group-major tm {tm:3d} tn {tn:4d}", out, dt, ref)
    for tm, tk, tn in MEGABLOX[kind]:
        tk = tk or k
        if m % tm or k % tk or n % tn:
            continue
        fn = jax.jit(lambda r, w, s, _t=(tm, tk, tn): gmm(r, w, s, preferred_element_type=jnp.bfloat16, tiling=_t))
        try:
            out, dt = timed(fn, rows, w, full_j)
        except Exception as ex:
            print(f"{name:20s} (a) megablox {tm, tk, tn}: FAILED {str(ex)[:200]}", flush=True)
            continue
        report(f"(a) megablox tm {tm:3d} tk {tk:4d} tn {tn:4d}", out, dt, ref)


for name in sys.argv[1:] or SHAPES:
    run(name)
