"""K-geometry GEMM ceiling probe (r3 weak #2: the chip-ceiling defense
rested on unrecorded probe numbers — this is the runnable record).

Measures sustained bf16 matmul TF/s as a function of the contraction
dimension K with M=N fixed, using the same methodology BASELINE.md
cites: a chained-carry fori_loop inside one jit (so XLA cannot dead-code
or overlap host latency), D2H-synced, loop overhead differenced out via
a zero-work baseline loop.

Why K matters: the MXU pipeline amortizes weight-load over K. A
transformer's hidden-size GEMMs (K = 768/1024) cannot reach the
K>=4096 peak — this probe quantifies that gap on the current chip, and
with it the per-model ceiling (e.g. GPT-2 345M: hidden 1024 -> the
K=1024 row bounds tokens/s).

Usage: python benchmarks/gemm_probe.py [--mn 4096] [--iters 32]
Prints one JSON line per K plus a summary.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timed_loop(k, m_rows, target_s=0.25):
    """TF/s for the bf16 [M,K]@[K,K] matmul — the transformer layer
    geometry (M = batch*seq tokens, N = K = hidden). The carry IS the
    activation matrix (a_{i+1} = (a_i @ b) * const), so iterations are
    truly serial: earlier probe shapes let XLA hoist the matmul
    (scalar-scaled lhs commutes), shrink it (single-element reads,
    slice pushdown), or factor it (sum(A@B) = colsum(A)@rowsum(B)) —
    all observed on-chip as impossible TF/s readings.

    Timing: the loop runs at two lengths n and 2n and the per-iter
    time is (t_2n - t_n)/n, which cancels the fixed host dispatch
    latency exactly; n is auto-sized so the loop body compute dwarfs
    its jitter.
    """
    a = jnp.asarray(np.random.RandomState(0).randn(m_rows, k),
                    jnp.bfloat16)
    b = jnp.asarray(
        np.random.RandomState(1).randn(k, k) / np.sqrt(k) * 0.5,
        jnp.bfloat16)
    flops = 2.0 * m_rows * k * k
    n = min(50000, max(64, int(target_s * 150e12 / flops)))

    def mk(iters):
        @jax.jit
        def chain(a, b):
            def body(_, carry):
                return ((carry @ b) * jnp.bfloat16(1.0009765625))

            return jax.lax.fori_loop(0, iters, body, a)

        return chain

    def run_sync(f):
        """Sync by device_get of one element of the result."""
        t0 = time.perf_counter()
        np.asarray(f(a, b)[0, 0])
        return time.perf_counter() - t0

    c1, c2 = mk(n), mk(2 * n)
    run_sync(c1)   # compile
    run_sync(c2)
    t1s = [run_sync(c1) for _ in range(3)]
    t2s = [run_sync(c2) for _ in range(3)]
    dt = max(float(np.median(t2s)) - float(np.median(t1s)), 1e-9) / n
    return flops / dt / 1e12, dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m-rows", type=int, default=4096,
                    help="token dimension M (batch*seq)")
    args = ap.parse_args()
    rows = []
    for k in (256, 512, 768, 1024, 2048, 4096, 8192):
        tfs, dt = _timed_loop(k, args.m_rows)
        rows.append({"K": k, "M": args.m_rows, "tflops": round(tfs, 1),
                     "step_ms": round(dt * 1e3, 3)})
        print(json.dumps(rows[-1]))
    peak = max(r["tflops"] for r in rows)
    k1024 = next(r["tflops"] for r in rows if r["K"] == 1024)
    frac = k1024 / peak
    print(json.dumps({
        "summary": "K-geometry GEMM sustained TF/s",
        "peak_tflops": peak,
        "k1024_tflops": k1024,
        "k1024_fraction_of_peak": round(frac, 3),
        "note": ("K=1024 GEMMs are geometry-bound; model ceilings "
                 "follow from the K=1024 row" if frac < 0.7 else
                 "K=1024 GEMMs run near peak: hidden-1024 models are "
                 "NOT GEMM-geometry-bound — profile the step "
                 "(profile_gpt2.py) for the real time sink"),
    }))


if __name__ == "__main__":
    main()
