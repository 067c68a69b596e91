"""Per-op micro-benchmark harness + regression record (r4 verdict
missing #5).

Parity target: paddle/fluid/operators/benchmark/op_tester.cc +
tools/ci_op_benchmark.sh — a config-driven per-op timing harness whose
JSON record lets the next round diff per-op performance instead of
discovering regressions at the model level.

Methodology (BASELINE.md r4 corrected-probe rules): ops are chained
serially inside one jitted lax.scan (XLA cannot batch or elide
iterations whose input depends on the previous output), timing uses
device-get syncs, and two scan lengths cancel the fixed host dispatch
latency: t = (T(2n) - T(n)) / n.

usage:
    python benchmarks/op_bench.py                  # run all, print
    python benchmarks/op_bench.py --save           # + write baseline
    python benchmarks/op_bench.py --check [--tol 0.25]
        # compare against the committed baseline; exit 1 on any op
        # slower than baseline*(1+tol) — the CI regression gate
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "artifacts",
                             "op_bench_baseline.json")


def jnp_sum_f32(a):
    import jax.numpy as jnp

    return jnp.sum(a.astype(jnp.float32))


def _chain_time(step_fn, init, n=16, reps=3, min_diff_s=0.03):
    """Serial-chain timing: median over `reps` of (T(2n)-T(n))/n.

    The chain length adapts upward until the measured difference
    clears the host dispatch jitter — a fixed short chain
    under-resolves cheap ops into noise (or 0)."""
    import jax

    @functools.partial(jax.jit, static_argnums=1)
    def chain(x0, length):
        def body(c, _):
            return step_fn(c), None

        out, _ = jax.lax.scan(body, x0, None, length=length)
        # sync value must depend on EVERY element: reading one element
        # lets XLA slice the whole elementwise chain down to scalar
        # ops (BASELINE.md corrected-probe rules). The extra reduce is
        # identical at both lengths, so (T(2n)-T(n)) cancels it.
        return jax.tree_util.tree_map(
            lambda a: jnp_sum_f32(a), out)

    def run(length):
        t0 = time.perf_counter()
        out = chain(init, length)
        _ = [float(np.asarray(o)) for o in
             jax.tree_util.tree_leaves(out)]  # device-get sync
        return time.perf_counter() - t0

    while True:
        run(n)
        run(2 * n)
        diff = min(run(2 * n) for _ in range(2)) - min(
            run(n) for _ in range(2))
        if diff >= min_diff_s or n >= 4096:
            break
        n *= 4
    ts_n = [run(n) for _ in range(reps)]
    ts_2n = [run(2 * n) for _ in range(reps)]
    return max((float(np.median(ts_2n)) - float(np.median(ts_n))) / n,
               1e-9)


def _f32(rng, *shape):
    import jax.numpy as jnp

    return jnp.asarray(rng.randn(*shape), jnp.float32)


def _bf16(rng, *shape):
    import jax.numpy as jnp

    return jnp.asarray(rng.randn(*shape), jnp.bfloat16)


def build_ops():
    """name -> (init_carry, step_fn, work_dict). step_fn must be
    shape-preserving on the carry (serial chain)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    ops = {}

    # -- MXU ----------------------------------------------------------
    # abs() in every linear chain: without a nonlinearity XLA folds
    # the unrolled iterations ((x@W)*c chains precompute to one
    # effective matrix; affine elementwise chains fold to one op)
    w1 = _bf16(rng, 1024, 1024)
    ops["matmul_4096x1024x1024_bf16"] = (
        _bf16(rng, 4096, 1024),
        lambda x: jnp.abs(x @ w1) * jnp.bfloat16(0.001),
        {"flops": 2 * 4096 * 1024 * 1024})
    w2 = _bf16(rng, 4096, 4096)
    ops["matmul_4096x4096x4096_bf16"] = (
        _bf16(rng, 4096, 4096),
        lambda x: jnp.abs(x @ w2) * jnp.bfloat16(0.0001),
        {"flops": 2 * 4096 * 4096 * 4096})
    kw = _bf16(rng, 3, 3, 256, 256)
    ops["conv2d_3x3_56x56x256_bf16"] = (
        _bf16(rng, 32, 56, 56, 256),
        lambda x: jnp.abs(jax.lax.conv_general_dilated(
            x, kw, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")))
        * jnp.bfloat16(0.01),
        {"flops": 2 * 32 * 56 * 56 * 256 * 256 * 9})

    # -- VPU / HBM ----------------------------------------------------
    big = _f32(rng, 4096, 4096)
    ops["add_abs_16M_f32"] = (big, lambda x: jnp.abs(x + 1.0),
                              {"bytes": 2 * big.nbytes})
    ops["multiply_abs_16M_f32"] = (
        big, lambda x: jnp.abs(x * 1.0000001) * -1.0,
        {"bytes": 2 * big.nbytes})
    ops["exp_16M_f32"] = (big * 1e-6, lambda x: jnp.exp(x) * 1e-6,
                          {"bytes": 2 * big.nbytes})
    ops["reduce_sum_16M_f32"] = (
        big, lambda x: jnp.abs(x + (jnp.sum(x) * 1e-20)),
        {"bytes": big.nbytes})
    ops["softmax_4096x4096_f32"] = (
        big, lambda x: jax.nn.softmax(x, axis=-1) + x * 1e-6,
        {"bytes": 4 * big.nbytes})
    ops["transpose_4096x4096_f32"] = (
        big, lambda x: jnp.abs(jnp.transpose(x)),
        {"bytes": 2 * big.nbytes})
    ln_w = _f32(rng, 1024)
    act = _bf16(rng, 4096, 1024)
    ops["layer_norm_4096x1024_bf16"] = (
        act,
        lambda x: ((x.astype(jnp.float32)
                    - jnp.mean(x.astype(jnp.float32), -1,
                               keepdims=True))
                   * jax.lax.rsqrt(
                       jnp.var(x.astype(jnp.float32), -1,
                               keepdims=True) + 1e-5)
                   * ln_w).astype(jnp.bfloat16),
        {"bytes": 2 * act.nbytes})

    # -- memory / indexing -------------------------------------------
    table = _f32(rng, 50304, 256)
    idx = np.random.RandomState(1).randint(0, 50304, (8192,))
    idx_j = jnp.asarray(idx, jnp.int32)
    def _gather(x):
        # indices derive from the carry so the take cannot hoist out
        # of the loop as a loop-invariant
        shift = jnp.int32(jnp.abs(x[0, 0]) * 1e-20)
        return jnp.take(table, idx_j + shift, axis=0) + x * 1e-6

    ops["gather_8192_of_50304x256"] = (
        _f32(rng, 8192, 256), _gather,
        {"bytes": 2 * 8192 * 256 * 4})
    ops["scatter_add_8192_into_50304x256"] = (
        table,
        lambda t: t.at[idx_j].add(jnp.float32(1e-7)),
        {"bytes": 2 * 8192 * 256 * 4})

    # -- fused attention ---------------------------------------------
    try:
        from paddle_tpu.incubate.nn.attention_pallas import (
            flash_attention)

        q = _bf16(rng, 4, 16, 1024, 64)
        kv = _bf16(rng, 4, 16, 1024, 64)

        def fa(x):
            o = flash_attention(x, kv, kv, True, 0.125)
            return (x + o * jnp.bfloat16(1e-6))

        ops["flash_attention_fwd_4x16x1024x64"] = (
            q, fa, {"flops": 2 * 2 * 4 * 16 * 1024 * 1024 * 64 // 2})
    except Exception:
        pass

    # -- fused layernorm->gelu vs the unfused XLA composition --------
    # (ISSUE 8 acceptance: the fused kernel must beat this twin on
    # TPU; on CPU both pallas entries record errors — the kernels are
    # TPU/interpret-only — and the gate skips unresolved entries)
    ln_w2 = _f32(rng, 1024)
    ln_b2 = _f32(rng, 1024)
    act2 = _bf16(rng, 4096, 1024)

    def _unfused_ln_gelu(x):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * ln_w2 + ln_b2
        return (jax.nn.gelu(y).astype(jnp.bfloat16)
                + x * jnp.bfloat16(1e-3))

    ops["layernorm_gelu_unfused_4096x1024_bf16"] = (
        act2, _unfused_ln_gelu, {"bytes": 2 * act2.nbytes})
    try:
        from paddle_tpu.incubate.nn.pallas.layernorm import (
            fused_layer_norm)

        def _fused_ln_gelu(x):
            y = fused_layer_norm(x, ln_w2, ln_b2, 1e-5, "gelu", True,
                                 False)
            return y + x * jnp.bfloat16(1e-3)

        ops["fused_layernorm_gelu_4096x1024_bf16"] = (
            act2, _fused_ln_gelu, {"bytes": 2 * act2.nbytes})
    except Exception:
        pass

    # -- fused multi-tensor adam update vs the plain composition -----
    G = 128  # 128 chunks x 32768 = 4.2M parameters
    p0 = _f32(rng, G, 256, 128)
    m0 = jnp.zeros((G, 256, 128), jnp.float32)
    v0 = jnp.zeros((G, 256, 128), jnp.float32)
    gk = _f32(rng, G, 256, 128) * jnp.float32(1e-2)
    d1c = jnp.full((G, 1), 0.1, jnp.float32)
    d2c = jnp.full((G, 1), 0.001, jnp.float32)

    def _unfused_adam(carry):
        p, m, v = carry
        m2 = 0.9 * m + 0.1 * gk
        v2 = 0.999 * v + 0.001 * gk * gk
        p2 = p - 1e-3 * (m2 / 0.1) / (jnp.sqrt(v2 / 0.001) + 1e-8)
        return (p2, m2, v2)

    ops["adam_update_unfused_4M"] = ((p0, m0, v0), _unfused_adam,
                                     {"bytes": 7 * p0.nbytes})
    try:
        from paddle_tpu.incubate.nn.pallas.optim import (
            fused_adam_chunks)
        wd0 = jnp.zeros((G, 1), jnp.float32)
        lr0 = jnp.float32(1e-3)

        def _fused_adam(carry):
            p, m, v = carry
            return fused_adam_chunks(p, gk, m, v, lr0, d1c, d2c, wd0,
                                     beta1=0.9, beta2=0.999, eps=1e-8)

        ops["fused_adam_update_4M"] = ((p0, m0, v0), _fused_adam,
                                       {"bytes": 7 * p0.nbytes})
    except Exception:
        pass
    return ops


def run_all(n=16):
    results = {}
    for name, (init, step, work) in build_ops().items():
        try:
            dt = _chain_time(step, init, n=n)
            if dt <= 2e-9:
                # the (T(2n)-T(n)) difference never cleared the timing
                # floor even at the max chain length: record the fact,
                # not a fake 0us/absurd-GBps number (review r5)
                results[name] = {"unresolved": True}
            else:
                rec = {"us": round(dt * 1e6, 2)}
                if "flops" in work:
                    rec["tflops"] = round(work["flops"] / dt / 1e12, 2)
                if "bytes" in work:
                    rec["gbps"] = round(work["bytes"] / dt / 1e9, 1)
                results[name] = rec
        except Exception as e:
            results[name] = {"error": f"{type(e).__name__}: "
                                      f"{str(e)[:160]}"}
        print("[op]", name, json.dumps(results[name]), flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--save", action="store_true",
                    help="write the baseline record")
    ap.add_argument("--check", action="store_true",
                    help="gate against the committed baseline")
    ap.add_argument("--tol", type=float, default=0.25)
    args = ap.parse_args()

    import jax

    platform = jax.devices()[0].platform
    results = run_all()
    out = {"platform": platform, "ops": results}
    if args.save:
        # merge: an unresolved/errored/0-rounded new measurement must
        # not evict a previously RESOLVED baseline entry; deltas vs
        # the old baseline print at the gate's own tolerance so a
        # --save cannot silently ratchet past a real regression, and
        # an op whose value moved by more than the tolerance across
        # clean re-saves of IDENTICAL code is marked volatile — the
        # gate then skips it loudly.
        if os.path.exists(BASELINE_PATH):
            with open(BASELINE_PATH) as f:
                prev = json.load(f).get("ops", {})
            for name, rec in list(out["ops"].items()):
                old_rec = prev.get(name, {})
                if rec.get("us", 0) <= 0 and old_rec.get("us", 0) > 0:
                    out["ops"][name] = old_rec
                    print(f"KEEP {name}: new run unresolved; keeping "
                          f"baseline {old_rec['us']}us",
                          file=sys.stderr)
                elif (rec.get("us", 0) > 0 and old_rec.get("us", 0) > 0
                      and abs(rec["us"] - old_rec["us"])
                      > args.tol * old_rec["us"]):
                    rec["volatile"] = True
                    print(f"DELTA {name}: {old_rec['us']}us -> "
                          f"{rec['us']}us (>{args.tol:.0%} on identical"
                          " code — marked volatile; the gate will "
                          "skip it loudly)", file=sys.stderr)
                elif old_rec.get("volatile") and rec.get("us", 0) > 0:
                    rec["volatile"] = True  # sticky until curated
        os.makedirs(os.path.dirname(BASELINE_PATH), exist_ok=True)
        with open(BASELINE_PATH, "w") as f:
            json.dump(out, f, indent=1)
        print(f"baseline written: {BASELINE_PATH}", file=sys.stderr)
    print(json.dumps(out))  # after the merge: stdout == written record
    if args.check:
        if not os.path.exists(BASELINE_PATH):
            print("no baseline to check against", file=sys.stderr)
            return 1
        with open(BASELINE_PATH) as f:
            base = json.load(f)
        if base.get("platform") != platform:
            print(f"baseline platform {base.get('platform')} != "
                  f"{platform}; skipping gate", file=sys.stderr)
            return 0
        bad = []
        for name, b in base["ops"].items():
            # iterate the BASELINE so a gated op that crashed or went
            # missing in the current run FAILS instead of vanishing
            rec = results.get(name)
            if b.get("us", 0) <= 0:
                print(f"SKIP {name}: no resolved baseline to gate "
                      "against", file=sys.stderr)
                continue
            if b.get("volatile"):
                print(f"SKIP {name}: baseline marked volatile "
                      "(unresolved across clean re-saves — see "
                      "--save DELTA)",
                      file=sys.stderr)
                continue
            if rec is None or "error" in rec:
                bad.append((name, b["us"],
                            rec.get("error", "missing from run")
                            if rec else "missing from run"))
            elif rec.get("us", 0) <= 0:
                bad.append((name, b["us"], "unresolved measurement"))
            elif rec["us"] > b["us"] * (1 + args.tol):
                bad.append((name, b["us"], f"{rec['us']}us"))
        for name, was, now in bad:
            print(f"REGRESSION {name}: {was}us -> {now}",
                  file=sys.stderr)
        return 1 if bad else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
