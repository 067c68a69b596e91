"""Flash-attention kernel micro-benchmark: forward, dq and dkv apart.

Times each of the three flash kernels of
`paddle_tpu/incubate/nn/attention_pallas.py` alone, on the chip, for a
sweep of (block_q, block_k), and prints for each the milliseconds a
call and the share of the least time the chip could take
(`tpubench/models/gpt2.py` `flash_flops_per_step`: the causal half of
two matmuls a kernel at the bf16 peak of `tpubench/peaks.json`).

    chiprun -- python benchmarks/attn_bench.py            # the cell's shape
    chiprun -- python benchmarks/attn_bench.py --shapes   # + D=128, long S
    chiprun -- python benchmarks/attn_bench.py --shape 3,16,4096,64
    python benchmarks/attn_bench.py --tree chip_scratch/parent  # another tree

A tree without `_flash_dq` / `_flash_dkv` (before PR 37) has its whole
backward timed as one row. `--shapes` ends with the residency line
moved both ways (`--set` moves any module constant); two designs are
compared as two trees (`--tree`), the program has no switch for one.
Timing: `<kernel>_ms` is the device
time of the call's Mosaic ops in a profiler trace of ten calls (what
`flash_roofline` reads), `_program_ms` that of the whole jitted program
around it (pads, the statistics' reshapes), `_wall_ms` the host's
clock: the call dispatched n and 2n times, t = (T(2n) - T(n)) / n, the
least of three.
"""
from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PAIRS = [(bq, bk) for bq in (128, 256, 512, 1024) for bk in (128, 256, 512)]


def time_call(fn, args, n=20):
    import jax

    def run(reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    run(2)  # compile + warm
    t_n = min(run(n) for _ in range(3))
    t_2n = min(run(2 * n) for _ in range(3))
    return (t_2n - t_n) / n


def time_fwd_bwd(attn_fn, B, H, S, D, n=8):
    """Forward + backward through the public entry, seconds a call
    (benchmarks/exp_encoder.py): serial chaining so XLA cannot batch or
    elide iterations, two loop lengths so the fixed host latency
    cancels."""
    import functools

    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    q0 = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    k0 = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    v0 = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)

    def loss(q, k, v):
        o = attn_fn(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * 1e-3)

    g = jax.value_and_grad(loss, argnums=(0, 1, 2))

    @functools.partial(jax.jit, static_argnums=2)
    def chain(q, k, length):
        def body(carry, _):
            qc, kc = carry
            l, (dq, dk, dv) = g(qc, kc, v0)
            # serial dependence: next iteration's inputs depend on this
            # iteration's grads in a way constant folding can't remove
            qc = q0 + (l.astype(jnp.bfloat16) * 1e-20) * dq
            kc = k0 + (l.astype(jnp.bfloat16) * 1e-20) * dk
            return (qc, kc), dv[0, 0, 0, 0]
        (qf, _), outs = jax.lax.scan(body, (q, k), None, length=length)
        return qf[0, 0, 0, 0] + jnp.sum(outs)

    def run(length):
        t0 = time.perf_counter()
        float(np.asarray(chain(q0, k0, length)))  # device-get sync
        return time.perf_counter() - t0

    run(n)       # compile n
    run(2 * n)   # compile 2n
    ts_n = min(run(n) for _ in range(3))
    ts_2n = min(run(2 * n) for _ in range(3))
    return (ts_2n - ts_n) / n


def device_ms(fn, args, n=10):
    """(the Mosaic calls', the whole program's) device milliseconds a
    call, from a profiler trace of n calls: what the benchmark's
    `flash_roofline` reads, with nothing of the host in it."""
    import jax

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            out = None
            for _ in range(n):
                out = fn(*args)
            jax.block_until_ready(out)
        (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        kernel = program = 0
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/device:TPU:0":
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    kernel = sum(e.duration_ns for e in line.events
                                 if "tpu_custom_call" in e.name)
                elif line.name == "XLA Modules":
                    program = sum(e.duration_ns for e in line.events)
    return kernel / n / 1e6, program / n / 1e6


def least_ms(b, h, s, d, causal, peak_flops):
    """Two matmuls of 2*S*S*D flops a head (half of it causal): what
    each of the three kernels cannot do without."""
    return 2 * 2 * b * h * s * s * d * (0.5 if causal else 1.0) \
        / peak_flops * 1e3


def kernels(ap, b, h, s, d, bq, bk, causal, dtype):
    """{name: (jitted fn, args)} for one shape and block pair."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    q, k, v, do = (jnp.asarray(rng.randn(b, h, s, d) * 0.5, dtype)
                   for _ in range(4))
    scale = 1.0 / float(np.sqrt(d))
    fwd = jax.jit(lambda q, k, v: ap._flash_fwd_impl(
        q, k, v, causal, scale, bq, bk))
    o, lse = fwd(q, k, v)
    out = {"fwd": (fwd, (q, k, v))}
    if not hasattr(ap, "_flash_dq"):
        out["bwd"] = (jax.jit(lambda q, k, v, o, lse, do: ap._flash_bwd_impl(
            q, k, v, o, lse, do, causal, scale, bq, bk)),
            (q, k, v, o, lse, do))
        return out
    bq_, _ = ap._block_and_pad(s, bq)
    bk_, _ = ap._block_and_pad(s, bk)
    flat = [x.reshape(b * h, s, d) for x in (q, k, v, do)]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(b * h, s)
    args = (*flat, lse.reshape(b * h, s), delta)
    out["dq"] = (jax.jit(lambda *a: ap._flash_dq(
        *a, causal, scale, bq_, bk_, None, False)[0]), args)
    out["dkv"] = (jax.jit(lambda *a: ap._flash_dkv(
        *a, causal, scale, bq_, bk_, None, False)[:2]), args)
    return out


def sweep(ap, shape, pairs, causal, dtype, peak, tag=""):
    b, h, s, d = shape
    least = least_ms(b, h, s, d, causal, peak)
    rows = []
    for bq, bk in pairs:
        row = {"shape": list(shape), "causal": causal, "bq": bq, "bk": bk,
               "tag": tag, "least_ms_a_kernel": round(least, 4)}
        total = 0.0
        try:
            for name, (fn, args) in kernels(ap, b, h, s, d, bq, bk, causal,
                                            dtype).items():
                row[name + "_wall_ms"] = round(time_call(fn, args) * 1e3, 4)
                ms, program = device_ms(fn, args)
                n_least = 2 if name == "bwd" else 1
                row[name + "_ms"] = round(ms, 4)
                row[name + "_program_ms"] = round(program, 4)
                row[name + "_share"] = round(
                    100 * n_least * least / max(ms, 1e-9), 2)
                total += ms
            row["sum_ms"] = round(total, 4)
            row["sum_share"] = round(100 * 3 * least / max(total, 1e-9), 2)
        except Exception as e:  # a pair the compiler refuses is a row too
            row["error"] = str(e).replace("\n", " ")[:160]
        rows.append(row)
        print("[attn]", json.dumps(row), flush=True)
    return rows


def main():
    ap_ = argparse.ArgumentParser()
    ap_.add_argument("--tree", default=ROOT,
                     help="checkout to import paddle_tpu from")
    ap_.add_argument("--shape", action="append", default=[],
                     metavar="B,H,S,D", help="causal shapes to time "
                     "(default: the train cells' 12,16,1024,64)")
    ap_.add_argument("--shapes", action="store_true",
                     help="also D=128, S=2048/4096/8192, non-causal")
    ap_.add_argument("--pairs", default="",
                     help="bq,bk;bq,bk (default: the whole sweep)")
    ap_.add_argument("--set", action="append", default=[],
                     metavar="NAME=INT", help="a module constant of "
                     "attention_pallas.py, e.g. _RESIDENT_BYTES=0 "
                     "(every shape streamed)")
    ap_.add_argument("--out", default="")
    a = ap_.parse_args()
    sys.path.insert(0, os.path.abspath(a.tree))
    import jax
    import jax.numpy as jnp

    ap = importlib.import_module("paddle_tpu.incubate.nn.attention_pallas")
    for item in a.set:
        name, _, value = item.partition("=")
        getattr(ap, name)           # a typo is an error, not a new name
        setattr(ap, name, int(value))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("attn_bench measures the chip; no TPU here")
    with open(os.path.join(ROOT, "tpubench", "peaks.json")) as f:
        peak = json.load(f)[dev.device_kind]["bf16_flops_per_s"]
    pairs = ([tuple(int(x) for x in p.split(",")) for p in a.pairs.split(";")]
             if a.pairs else PAIRS)
    bf16 = jnp.bfloat16
    cell = (12, 16, 1024, 64)
    rows = []
    for shape in a.shape or ["12,16,1024,64"]:
        rows += sweep(ap, tuple(int(x) for x in shape.split(",")), pairs,
                      True, bf16, peak)
    if a.shapes:
        for shape in ((6, 16, 1024, 128), (6, 16, 2048, 64),
                      (3, 16, 4096, 64), (3, 16, 4096, 128),
                      (2, 16, 8192, 64)):
            rows += sweep(ap, shape, pairs, True, bf16, peak)
        rows += sweep(ap, cell, pairs, False, bf16, peak)
        if hasattr(ap, "_RESIDENT_SCORES"):
            # where the residency line falls: the cell's and 2048 keys
            # streamed in blocks, 4096 held whole and unrolled
            keep = ap._RESIDENT_BYTES, ap._RESIDENT_SCORES
            ap._RESIDENT_BYTES = 0
            for shape in (cell, (6, 16, 2048, 64)):
                rows += sweep(ap, shape, pairs, True, bf16, peak,
                              tag="streamed-forced")
            ap._RESIDENT_BYTES, ap._RESIDENT_SCORES = keep[0], 4096 * 4096
            rows += sweep(ap, (3, 16, 4096, 64), pairs, True, bf16, peak,
                          tag="resident-forced")
            ap._RESIDENT_BYTES, ap._RESIDENT_SCORES = keep
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"device": dev.device_kind, "tree": a.tree,
                       "set": a.set, "rows": rows}, f, indent=1)
    print(json.dumps({"device": dev.device_kind, "rows": len(rows)}))


if __name__ == "__main__":
    main()
