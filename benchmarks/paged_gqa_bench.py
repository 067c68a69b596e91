"""Paged K/V attention kernel micro-benchmark: one launch at each cell's shape.

Times one launch of the paged K/V decode kernel of
`paddle_tpu/incubate/nn/pallas/paged_attention.py` (`paged_attention`)
alone, on the chip, at the shapes the serving cells give it, for a sweep
of the rows a page group holds, and prints for each the milliseconds a
launch, the live K/V bytes over that time and their share of the HBM's
peak (`tpubench/peaks.json`), and the largest difference from the dense
reference (`paged_attention_reference`):

    python benchmarks/paged_gqa_bench.py  # on one TPU chip
    python benchmarks/paged_gqa_bench.py --rows 512 --shape lfm2
    python benchmarks/paged_gqa_bench.py --tree chip_scratch/parent  # a tree

Shapes (pages of 16 rows; the contexts drawn from `--seed`, uniform in the
range the cell's window holds):

    mellum_full    96 x 7.1k-8.1k tokens, 32 query over 4 K/V heads of 128, bf16
    mellum_window  the same through `window=1024`
    falconh1       128 x 1.8k-2.8k, 20 over 4 of 128, bf16
    lfm2           256 x 1.8k-2.6k, 32 over 8 of 64, bf16
    gpt2           32 x 300-650, 16 heads of 64, float32

The rows a group are set by replacing the module's rule for the sweep:
for a shape with a window, on a tree that counts a windowed call's groups
from the window's first page, `_window_pages` (1040 rows: one group a
sequence; 1024: a group and a tail of one page), else `_pages_per_group`
(on an older tree a windowed call's groups are counted from position 0);
`rule_rows` is what the tree's own rule gives. Two designs
of the kernel's body are compared as two trees (`--tree`): the program has
no switch for one. Timing: `ms` is the device time of the launch's Mosaic
op in a profiler trace of ten launches, nothing of the host in it.
"""
from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BS = 16
# name: (sequences, query heads, K/V heads, head dim, dtype, contexts, window)
SHAPES = {
    "mellum_full": (96, 32, 4, 128, "bfloat16", (7100, 8100), None),
    "mellum_window": (96, 32, 4, 128, "bfloat16", (7100, 8100), 1024),
    "falconh1": (128, 20, 4, 128, "bfloat16", (1800, 2800), None),
    "lfm2": (256, 32, 8, 64, "bfloat16", (1800, 2600), None),
    "gpt2": (32, 16, 16, 64, "float32", (300, 650), None),
}


def inputs(shape, seed):
    """q, the two pools stored `[N, BS, Hkv*D]` as the cache stores them,
    the tables (each sequence's blocks scattered over the pool, NULL block
    0 past its context) and the contexts."""
    import jax
    import jax.numpy as jnp

    b, hq, hkv, d, dtype, (lo, hi), _ = shape
    rng = np.random.RandomState(seed)
    lens = rng.randint(lo, hi + 1, size=b).astype(np.int32)
    used = -(-lens // BS)
    maxb = int(used.max())
    n = int(used.sum()) + 1
    ids = 1 + rng.permutation(n - 1)
    tables = np.zeros((b, maxb), np.int32)
    at = 0
    for i, u in enumerate(used):
        tables[i, :u] = ids[at:at + u]
        at += u
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed % 2 ** 31), 3)
    q = jax.random.normal(kq, (b, hq, d), dtype)
    k = jax.random.normal(kk, (n, BS, hkv * d), dtype)
    v = jax.random.normal(kv, (n, BS, hkv * d), dtype)
    return q, k, v, jnp.asarray(tables), jnp.asarray(lens)


def live_bytes(shape, lens):
    """K and V bytes of the pages that hold a token the query sees: what
    the launch cannot do without reading."""
    _, _, hkv, d, dtype, _, window = shape
    lens = np.asarray(lens, np.int64)
    first = 0 if window is None else np.maximum(lens - window, 0) // BS
    pages = -(-lens // BS) - first
    return int(pages.sum()) * BS * hkv * d * np.dtype(dtype).itemsize * 2


def device_ms(fn, args, n=10):
    """Device milliseconds a launch of the Mosaic call, from a profiler
    trace of n launches."""
    import jax

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            out = None
            for _ in range(n):
                out = fn(*args)
            jax.block_until_ready(out)
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        total = 0
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/device:TPU:0":
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    total = sum(e.duration_ns for e in line.events
                                if "tpu_custom_call" in e.name)
    return total / n / 1e6


def rule_name(pa, shape):
    """The module's function that sets the shape's rows a group."""
    window = shape[-1]
    return "_window_pages" if window is not None \
        and hasattr(pa, "_window_pages") else "_pages_per_group"


def rule_rows(pa, shape):
    _, _, hkv, d, dtype, _, window = shape
    row_bytes = hkv * d * np.dtype(dtype).itemsize
    if rule_name(pa, shape) == "_window_pages":
        return pa._window_pages(window, BS, row_bytes) * BS
    try:
        pages = pa._pages_per_group(BS, row_bytes)
    except TypeError:                 # a tree whose rule reads the block alone
        pages = pa._pages_per_group(BS)
    return pages * BS


def sweep(pa, name, rows_list, seed, peak_bytes):
    import jax
    import jax.numpy as jnp

    shape = SHAPES[name]
    b, hq, hkv, d, dtype, _, window = shape
    q, k, v, tables, lens = inputs(shape, seed)
    scale = d ** -0.5

    pool = (k.shape[0], BS, hkv, d)
    want = jax.jit(lambda q, k, v, t, c: pa.paged_attention_reference(
        q, k.reshape(pool), v.reshape(pool), t, c, sm_scale=scale,
        window=window))(q, k, v, tables, lens)
    want = np.asarray(want.astype(jnp.float32))
    moved = live_bytes(shape, np.asarray(lens))
    name_of_rule = rule_name(pa, shape)
    rule = getattr(pa, name_of_rule)
    out = []
    try:
        for rows in rows_list:
            row = {"shape": name, "rows": rows, "rule_rows": None,
                   "seqs": b, "heads": [hq, hkv, d], "dtype": dtype,
                   "window": window, "ctx_median": int(np.median(lens)),
                   "live_gb": round(moved / 1e9, 4)}
            setattr(pa, name_of_rule, rule)
            row["rule_rows"] = rule_rows(pa, shape)
            if name_of_rule == "_window_pages":
                pa._window_pages = lambda _, bs, *__, r=rows: max(1, r // bs)
            else:
                pa._pages_per_group = lambda bs, *_, r=rows: max(1, r // bs)

            def call(q, k, v, tables, lens):
                # a function of its own a size (jit's trace cache is keyed
                # by it); the pools reshaped inside, where the view folds
                return pa.paged_attention(
                    q, k.reshape(pool), v.reshape(pool), tables, lens,
                    sm_scale=scale, window=window)

            try:
                fn = jax.jit(call)
                got = np.asarray(fn(q, k, v, tables, lens).astype(jnp.float32))
                ms = device_ms(fn, (q, k, v, tables, lens))
                row.update(ms=round(ms, 4),
                           gb_s=round(moved / ms / 1e6, 1),
                           hbm_share=round(100 * moved / ms / 1e-3
                                           / peak_bytes, 2),
                           max_err=float(np.max(np.abs(got - want))))
            except Exception as e:  # a size the compiler refuses is a row too
                row["error"] = str(e).replace("\n", " ")[:200]
            print("[paged]", json.dumps(row), flush=True)
            out.append(row)
    finally:
        setattr(pa, name_of_rule, rule)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=ROOT,
                    help="checkout to import paddle_tpu from")
    ap.add_argument("--shape", action="append", default=[],
                    choices=sorted(SHAPES), help="default: every shape")
    ap.add_argument("--rows", default="128,256,512,1024",
                    help="rows a page group, comma-separated")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    sys.path.insert(0, os.path.abspath(a.tree))
    import jax

    pa = importlib.import_module(
        "paddle_tpu.incubate.nn.pallas.paged_attention")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("paged_gqa_bench measures the chip; no TPU here")
    with open(os.path.join(ROOT, "tpubench", "peaks.json")) as f:
        peak = json.load(f)[dev.device_kind]["hbm_bytes_per_s"]
    rows_list = [int(r) for r in a.rows.split(",")]
    rows = []
    for name in a.shape or list(SHAPES):
        rows += sweep(pa, name, rows_list, a.seed, peak)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"device": dev.device_kind, "tree": a.tree,
                       "rows": rows}, f, indent=1)
    print(json.dumps({"device": dev.device_kind, "tree": a.tree,
                      "rows": len(rows)}))


if __name__ == "__main__":
    main()
