"""Pure-JAX ResNet-50 parity benchmark (r3 weak #2: the "framework is
at raw-JAX parity" claim rested on an unrecorded probe — this is the
runnable record).

A from-scratch jax/lax ResNet-50 (NHWC, bf16 activations, fp32 BN
statistics, SGD+momentum fwd+bwd train step) with NO paddle_tpu imports
— an independent ceiling for what any framework gets out of XLA on this
chip at the same batch/shape. Compare its imgs/s with bench.py's
`resnet50` config: parity (within jitter) means the framework layer
adds no overhead; a gap means framework overhead to chase.

Usage: python benchmarks/parity_resnet_jax.py [--batch 128] [--steps 60]
Prints one JSON line.
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

CFG50 = [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2),
         (3, 512, 2048, 2)]  # (blocks, width, out, first-stride)


def _conv(x, w, stride=1):
    # bf16 in/out (no preferred_element_type: an f32 primal output
    # hands the conv transpose an f32 cotangent against bf16 operands,
    # which lax rejects)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, scale, bias, training=True):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(xf), axis=(0, 1, 2)) - jnp.square(mean)
    y = (xf - mean) * jax.lax.rsqrt(var + 1e-5) * scale + bias
    return y.astype(x.dtype)


def _bottleneck(x, p, stride):
    idt = x
    y = jax.nn.relu(_bn(_conv(x, p["w1"]), p["s1"], p["b1"]))
    y = jax.nn.relu(_bn(_conv(y, p["w2"], stride), p["s2"], p["b2"]))
    y = _bn(_conv(y, p["w3"]), p["s3"], p["b3"])
    if "wd" in p:
        idt = _bn(_conv(x, p["wd"], stride), p["sd"], p["bd"])
    return jax.nn.relu(y + idt)


def init_params(rng):
    def conv_w(key, kh, kw, cin, cout):
        fan = kh * kw * cin
        return (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
                * np.sqrt(2.0 / fan)).astype(jnp.bfloat16)

    keys = iter(jax.random.split(rng, 256))
    params = {"stem_w": conv_w(next(keys), 7, 7, 3, 64),
              "stem_s": jnp.ones(64), "stem_b": jnp.zeros(64)}
    cin = 64
    for si, (blocks, width, cout, stride0) in enumerate(CFG50):
        for bi in range(blocks):
            p = {}
            stride = stride0 if bi == 0 else 1
            p["w1"] = conv_w(next(keys), 1, 1, cin, width)
            p["w2"] = conv_w(next(keys), 3, 3, width, width)
            p["w3"] = conv_w(next(keys), 1, 1, width, cout)
            for t in ("1", "2", "3"):
                c = {"1": width, "2": width, "3": cout}[t]
                p[f"s{t}"] = jnp.ones(c)
                p[f"b{t}"] = jnp.zeros(c)
            if bi == 0:
                p["wd"] = conv_w(next(keys), 1, 1, cin, cout)
                p["sd"] = jnp.ones(cout)
                p["bd"] = jnp.zeros(cout)
            params[f"s{si}b{bi}"] = p
            cin = cout
    params["fc_w"] = (jax.random.normal(next(keys), (2048, 1000),
                                        jnp.float32) * 0.01
                      ).astype(jnp.bfloat16)
    params["fc_b"] = jnp.zeros(1000, jnp.float32)
    return params


def forward(params, x):
    y = jax.nn.relu(_bn(_conv(x, params["stem_w"], 2),
                        params["stem_s"], params["stem_b"]))
    y = jax.lax.reduce_window(
        y, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for si, (blocks, _, _, stride0) in enumerate(CFG50):
        for bi in range(blocks):
            y = _bottleneck(y, params[f"s{si}b{bi}"],
                            stride0 if bi == 0 else 1)
    y = jnp.mean(y.astype(jnp.float32), axis=(1, 2))
    return y @ params["fc_w"].astype(jnp.float32) + params["fc_b"]


def loss_fn(params, x, labels):
    logits = forward(params, x)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None],
                                         axis=1))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def train_step(params, momentum, x, labels, lr=0.01, mu=0.9):
    loss, grads = jax.value_and_grad(loss_fn)(params, x, labels)

    def upd(p, m, g):
        m2 = mu * m + g.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * m2).astype(p.dtype), m2

    flat_p, tree = jax.tree_util.tree_flatten(params)
    flat_m = jax.tree_util.tree_leaves(momentum)
    flat_g = jax.tree_util.tree_leaves(grads)
    new = [upd(p, m, g) for p, m, g in zip(flat_p, flat_m, flat_g)]
    params = jax.tree_util.tree_unflatten(tree, [a for a, _ in new])
    momentum = jax.tree_util.tree_unflatten(tree, [b for _, b in new])
    return params, momentum, loss


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--windows", type=int, default=5)
    args = ap.parse_args()
    rng = np.random.RandomState(0)
    params = init_params(jax.random.PRNGKey(0))
    momentum = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    x = jnp.asarray(rng.randn(args.batch, 224, 224, 3), jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, 1000, (args.batch,)), jnp.int32)
    params, momentum, loss = train_step(params, momentum, x, labels)
    float(np.asarray(loss))  # compile + sync (device-get)
    dts = []
    for _ in range(args.windows):
        t0 = time.perf_counter()
        for _ in range(args.steps):
            params, momentum, loss = train_step(params, momentum, x,
                                                labels)
        float(np.asarray(loss))
        dts.append((time.perf_counter() - t0) / args.steps)
    dt = float(np.median(dts))
    print(json.dumps({
        "metric": "pure_jax_resnet50_imgs_per_sec",
        "value": round(args.batch / dt, 1),
        "unit": "imgs/s",
        "batch": args.batch,
        "window_spread": [round(d, 6) for d in dts],
        "note": "independent raw-XLA ceiling; compare bench.py resnet50",
    }))


if __name__ == "__main__":
    main()
