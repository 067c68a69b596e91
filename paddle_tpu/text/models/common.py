"""What the decoders served by `inference/serving/state_runner.py`
share (`glm4_moe_lite`, `longcat_flash`, `lfm2_moe`, `mellum`,
`falcon_h1`): the seeded parameter tree and the pieces of a block in
pure `jax.numpy` that more than one of them reads. Each model module
imports from here and from no other model's module; the latent
attention's mathematics is `mla.py`'s.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.tensor import Parameter
from ...nn.layer.layers import Layer
from ...ops import random as _random

__all__ = ["rms_norm", "rotate", "embed", "logits", "swiglu",
           "attend_dense", "SeededTree"]


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return xf.astype(x.dtype) * w


def rotate(x, positions, theta):
    """Rotary embedding over the last dimension of `x [..., D]`,
    half-split pairing; `positions` has x's leading shape or
    broadcasts against it (a heads axis is `positions[..., None]`)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def embed(params, ids, cfg):
    """The embedding rows of `ids` (a model with an embedding
    multiplier has an `embed` of its own)."""
    return jnp.take(params["embed"], ids, axis=0)


def logits(params, x, cfg):
    """Final norm and the untied head, float32."""
    x = rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
    return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


def swiglu(u, w13, w2):
    gate, up = jnp.split(u @ w13, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w2


def _times(x, scale):
    """`x * scale` in x's dtype; a scale of 1 leaves the program as
    it was."""
    return x if scale == 1 else x * jnp.asarray(scale, x.dtype)


def attend_dense(q, k, v, window=None, q_block=512, k_block=1024):
    """Causal attention of S tokens over themselves, grouped heads:
    q [S, Hq, D], k / v [S, Hkv*D] -> [S, Hq*D]; with a `window`,
    query `i` sees keys `i - window < j <= i`. Queries in blocks (the
    largest power of two under `q_block` that divides S), and a query
    block meets its keys a block of `k_block` at a time, from the one
    that holds the oldest key one of its queries can see to the one
    that holds its newest, under a running softmax (maximum, sum and
    weighted values in float32): the key blocks ahead of a query
    block, and those behind its window, are never read, so that a
    window layer's attention is linear in S and a full layer's half
    the square, and no score tile is wider than `k_block` (a
    `[heads, 512, 8192]` float32 tile costs a v5e 56 ms where eight
    of 1024 columns cost 3: PERF.md, section 6)."""
    s, hq, d = q.shape
    hkv = k.shape[-1] // d
    k, v = k.reshape(s, hkv, d), v.reshape(s, hkv, d)
    qg = q.reshape(s, hkv, hq // hkv, d)
    qb = math.gcd(s, 1 << (max(1, q_block).bit_length() - 1))
    kb = math.gcd(s, 1 << (max(1, k_block).bit_length() - 1))
    rows = (hkv, hq // hkv, qb)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i, qb)
        q_pos = (i + jnp.arange(qb))[:, None]

        def tile(j, carry):
            m, l, acc = carry
            ki = jax.lax.dynamic_slice_in_dim(k, j * kb, kb)
            vi = jax.lax.dynamic_slice_in_dim(v, j * kb, kb)
            scores = jnp.einsum("qkgd,skd->kgqs", qi, ki,
                                preferred_element_type=jnp.float32)
            behind = q_pos - (j * kb + jnp.arange(kb))
            seen = behind >= 0
            if window is not None:
                seen &= behind < window
            scores = jnp.where(seen, scores / math.sqrt(d), -1e30)
            # a row that has met no visible key yet weighs its masked
            # ones 1; its first visible key's `alpha` is an exact 0
            m_new = jnp.maximum(m, scores.max(-1))
            p = jnp.exp(scores - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            acc = acc * alpha[..., None] + jnp.einsum(
                "kgqs,skd->kgqd", p.astype(vi.dtype), vi,
                preferred_element_type=jnp.float32)
            return m_new, l * alpha + p.sum(-1), acc

        first = 0 if window is None \
            else jnp.maximum(i - window + 1, 0) // kb
        _, l, acc = jax.lax.fori_loop(
            first, (i + qb - 1) // kb + 1, tile,
            (jnp.full(rows, -1e30, jnp.float32),
             jnp.zeros(rows, jnp.float32),
             jnp.zeros(rows + (d,), jnp.float32)))
        return jnp.moveaxis(acc / l[..., None], 2, 0).astype(v.dtype)

    out = block(0) if qb == s else jax.lax.map(block, jnp.arange(0, s, qb))
    return out.reshape(s, hq * d)


class SeededTree(Layer):
    """A model whose parameters are one tree of leaves (stacked with a
    leading layer axis, run by `lax.scan`, or one a layer), drawn on
    the device in the configured dtype, one layer at a time: at the
    published widths a float32 construction of a few expert layers
    would not fit a 16 GB chip. `config` has `dtype` and
    `initializer_range`."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        self._dtype = jnp.dtype(config.dtype)
        self._key = _random.next_key()
        self._n_leaf = 0
        self._tree = {}

    def _add(self, name, value):
        self._n_leaf += 1
        p = Parameter(value, name=f"{name}_{self._n_leaf}")
        self.add_parameter(f"{name}_{self._n_leaf}", p)
        return p

    def _ones(self, name, shape):
        return self._add(name, jnp.ones(shape, self._dtype))

    def _normal(self, name, shape, layered=True, dtype=None, std=None):
        """`std` (initializer_range) x normal, drawn on the device in
        the target dtype, one slice of the leading (layer) axis at a
        time: a leaf never exists in float32 as a whole."""
        dtype = dtype or self._dtype
        std = std or self.config.initializer_range
        key = jax.random.fold_in(self._key, self._n_leaf)

        def draw(k, sh):
            return (std * jax.random.normal(k, sh, jnp.float32)
                    ).astype(dtype)

        if layered:
            value = jax.jit(lambda ks: jax.lax.map(
                lambda k: draw(k, shape[1:]), ks))(
                    jax.random.split(key, shape[0]))
        else:
            value = jax.jit(lambda k: draw(k, shape))(key)
        return self._add(name, value)

    def _params_tree(self):
        return self._tree
