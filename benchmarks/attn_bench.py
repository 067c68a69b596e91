"""Flash-attention kernel micro-benchmark (r5 perf round).

Times fwd+bwd of causal attention at the GPT-2 bench shape for:
  * the repo Pallas kernel (incubate/nn/attention_pallas.py) at a
    sweep of (block_q, block_k)
  * jax's reference TPU Pallas flash kernel (public jax library code)
  * XLA dense attention (the O(S^2)-memory fallback)

Methodology per the repo's corrected-probe rules (BASELINE.md r4):
device-get syncs, serial chaining so XLA can't batch/elide
iterations, and two loop lengths so the fixed host dispatch latency
cancels: t = (T(2n) - T(n)) / n.
"""
from __future__ import annotations

import functools
import json
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp


def time_fwd_bwd(attn_fn, B, H, S, D, n=8):
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


def main():
    B, H, S, D = 4, 16, 1024, 64
    # causal fwd ~2*2*B*H*S^2*D/2 FLOPs; bwd ~2.5x fwd
    fwd_fl = 2 * 2 * B * H * S * S * D * 0.5
    tot_fl = fwd_fl * 3.5
    results = {}

    from paddle_tpu.incubate.nn.attention_pallas import flash_attention

    for bq, bk in [(256, 256), (512, 512), (512, 256), (1024, 512),
                   (256, 512), (1024, 1024)]:
        name = f"repo_bq{bq}_bk{bk}"
        try:
            fn = lambda q, k, v: flash_attention(  # noqa: E731
                q, k, v, True, 1.0 / np.sqrt(D), bq, bk)
            dt = time_fwd_bwd(fn, B, H, S, D)
            results[name] = {"ms": round(dt * 1e3, 3),
                             "tflops": round(tot_fl / dt / 1e12, 1)}
        except Exception as e:
            results[name] = {"error": str(e)[:200]}
        print("[attn]", name, json.dumps(results[name]), flush=True)

    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_fa)

        fn = lambda q, k, v: jax_fa(  # noqa: E731
            q, k, v, causal=True, sm_scale=1.0 / float(np.sqrt(D)))
        dt = time_fwd_bwd(fn, B, H, S, D)
        results["jax_pallas"] = {"ms": round(dt * 1e3, 3),
                                 "tflops": round(tot_fl / dt / 1e12, 1)}
    except Exception as e:
        results["jax_pallas"] = {"error": str(e)[:200]}
    print("[attn] jax_pallas", json.dumps(results["jax_pallas"]),
          flush=True)

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
        s = s / np.sqrt(D)
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    try:
        dt = time_fwd_bwd(dense, B, H, S, D)
        results["xla_dense"] = {"ms": round(dt * 1e3, 3),
                                "tflops": round(tot_fl / dt / 1e12, 1)}
    except Exception as e:
        results["xla_dense"] = {"error": str(e)[:200]}
    print("[attn] xla_dense", json.dumps(results["xla_dense"]),
          flush=True)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
