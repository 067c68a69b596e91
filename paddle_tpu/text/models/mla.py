"""Latent attention (MLA) as DeepSeek-V2 published it, and what the
models built on it share (`glm4_moe_lite`, `longcat_flash`): the
block's mathematics in pure `jax.numpy`, which the serving runner
(`inference/serving/mla_runner.py`) reads too, and the seeded
parameter tree.

`N` being RMSNorm (weight, no bias), every projection without bias:
`c_q = N(u W_qa)`, `q = a_q c_q W_qb` per head `[nope | rope]`;
`[c_kv | k_r] = u W_kva`, `c_kv = a_kv N(c_kv)`; rotary (theta
`rope_theta`, no scaling) over the rope dims of `q` and over `k_r`,
which all heads share; `[k_nope | v] = c_kv W_kvb` per head; causal
softmax of `[q_nope | q_rope] . [k_nope | k_rope] / sqrt(nope +
rope)`; heads concatenated, times `W_o`. `a_q` and `a_kv` are the
config's `mla_q_scale` and `mla_kv_scale`: 1 for GLM-4.7-Flash,
`(hidden / rank)^0.5` for LongCat-Flash (`mla_scale_q_lora`,
`mla_scale_kv_lora`).

What a cache has to hold of a token is `[c_kv | k_rope]` alone
(`kv_lora_rank + qk_rope_head_dim` values an attention, the scale
inside): `mla_latent` makes that row, `mla_attend_dense` expands it
through `W_kvb` (prefill, training), `mla_attend_absorbed` folds
`W_kvb` into the query and the output and reads nothing but the rows
(decode; `mla_attend_paged` is the same through a paged pool's block
tables, in a Pallas kernel).

Assumed where the configs are silent: the rotary pairing is
half-split (dims `i` and `i + rope/2` rotate together); with seeded
weights the interleaved pairing is a column permutation of `W_qb`
and `W_kva`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.tensor import Parameter
from ...nn.layer.layers import Layer
from ...ops import random as _random

__all__ = ["rms_norm", "rotate", "embed", "swiglu", "mla_query", "mla_latent",
           "mla_attend_dense", "mla_attend_absorbed", "mla_attend_paged",
           "attention_block",
           "SeededTree"]


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


def swiglu(u, w13, w2):
    gate, up = jnp.split(u @ w13, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w2


def _times(x, scale):
    """`x * scale` in x's dtype; a scale of 1 leaves the program as
    it was."""
    return x if scale == 1 else x * jnp.asarray(scale, x.dtype)


def mla_query(u, ap, cfg, positions):
    """(q_nope [..., H, nope], q_rope [..., H, rope], rotated), both
    times `cfg.mla_q_scale`."""
    c_q = rms_norm(u @ ap["wq_a"], ap["q_norm"], cfg.rms_norm_eps)
    q = _times(c_q @ ap["wq_b"], cfg.mla_q_scale).reshape(
        u.shape[:-1] + (cfg.num_heads,
                        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    return q_nope, rotate(q_rope, positions[..., None], cfg.rope_theta)


def mla_latent(u, ap, cfg, positions):
    """The row a cache holds of each token: `[a_kv N(c_kv) | R(k_r)]`,
    `[..., kv_lora_rank + qk_rope_head_dim]`."""
    c_kv, k_r = jnp.split(u @ ap["wkv_a"], [cfg.kv_lora_rank], axis=-1)
    c_kv = _times(rms_norm(c_kv, ap["kv_norm"], cfg.rms_norm_eps),
                  cfg.mla_kv_scale)
    return jnp.concatenate(
        [c_kv, rotate(k_r, positions, cfg.rope_theta)], -1)


def _wkv_b(ap, cfg):
    """W_kvb as (W^K [rank, H, nope], W^V [rank, H, v])."""
    w = ap["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                            cfg.qk_nope_head_dim + cfg.v_head_dim)
    return jnp.split(w, [cfg.qk_nope_head_dim], axis=-1)


def _sm_scale(cfg):
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def mla_attend_dense(q_nope, q_rope, latent, ap, cfg):
    """Causal attention of S tokens over themselves, keys and values
    expanded from the latent rows through W_kvb. q_* [S, H, .],
    latent [S, row] -> [S, H * v_head_dim]."""
    s = latent.shape[0]
    c_kv, k_rope = jnp.split(latent, [cfg.kv_lora_rank], axis=-1)
    wk, wv = _wkv_b(ap, cfg)
    k_nope = jnp.einsum("sc,chd->shd", c_kv, wk)
    v = jnp.einsum("sc,chd->shd", c_kv, wv)
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope,
                           preferred_element_type=jnp.float32))
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(
        jnp.where(mask, scores * _sm_scale(cfg), -1e30), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v)
    return out.reshape(s, -1)


def _absorbed_query(q_nope, q_rope, wk, width):
    """`[q_nope W^K^T | q_rope]` [B, H, width]: the query in the
    cached rows' own space. A cache may store its rows wider than
    they are (zero-padded to a multiple of the device's lanes):
    zeros against zeros."""
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, wk)
    q = jnp.concatenate([q_lat, q_rope], -1)
    return jnp.pad(q, ((0, 0), (0, 0), (0, width - q.shape[-1])))


def _expanded(o_lat, wv):
    """`o_lat W^V`, heads concatenated: [B, H, rank] -> [B, H * v]."""
    out = jnp.einsum("bhc,chd->bhd", o_lat, wv)
    return out.reshape(out.shape[0], -1)


def mla_attend_absorbed(q_nope, q_rope, ctx, lens, ap, cfg):
    """Attention of one query token a sequence over cached latent
    rows, W_kvb absorbed: `q_lat = q_nope W^K^T`, scores over the
    rows as they are, `o = (P c_kv) W^V`. q_* [B, H, .], ctx
    [B, T, row] (positions >= lens[b] masked) -> [B, H * v_head_dim].
    The same mathematics as `mla_attend_dense`."""
    wk, wv = _wkv_b(ap, cfg)
    q = _absorbed_query(q_nope, q_rope, wk, ctx.shape[-1])
    scores = jnp.einsum("bhr,btr->bht", q, ctx,
                        preferred_element_type=jnp.float32)
    live = jnp.arange(ctx.shape[1])[None, :] < lens[:, None]
    probs = jax.nn.softmax(
        jnp.where(live[:, None, :], scores * _sm_scale(cfg), -1e30),
        axis=-1)
    o_lat = jnp.einsum("bht,btr->bhr", probs.astype(ctx.dtype),
                       ctx)[..., :cfg.kv_lora_rank]
    return _expanded(o_lat, wv)


def mla_attend_paged(q_nope, q_rope, pool, block_tables, lens, ap, cfg,
                     interpret=False):
    """`mla_attend_absorbed` THROUGH the block tables: pool
    [N, BS, row], `block_tables [B, MAXB]` naming each sequence's
    pages in it. The Pallas kernel reads the live rows once, each
    page both as keys and as values (`paged_latent_attention`);
    absorbing W^K and expanding through W^V stay here."""
    from ...incubate.nn.pallas.paged_attention import \
        paged_latent_attention

    wk, wv = _wkv_b(ap, cfg)
    q = _absorbed_query(q_nope, q_rope, wk, pool.shape[-1])
    o = paged_latent_attention(q, pool, block_tables, lens,
                               sm_scale=_sm_scale(cfg),
                               interpret=interpret)
    return _expanded(o[..., :cfg.kv_lora_rank], wv)


def attention_block(x, carry, ap, a, attend, eps):
    """One attention and the residual around it, inside a model's
    `layers`: `h = x + attend(N(x)) W_o`. `attend(u, carry, ap, a) ->
    (attention output [T, heads * v], carry, row)` is the calling
    program's own (dense over the prompt, or absorbed through the
    pool, which is then the carry); `a` is the attention's number in
    the cache. Returns (h, N(h) for the FFN that follows, carry,
    row)."""
    with jax.named_scope("mla/attend"):
        attn, carry, row = attend(rms_norm(x, ap["ln1"], eps), carry, ap, a)
    h = x + attn @ ap["wo"]
    return h, rms_norm(h, ap["ln2"], eps), carry, row


class SeededTree(Layer):
    """A model whose parameters are one tree of stacked leaves (a
    leading layer axis, run by `lax.scan`), drawn on the device in
    the configured dtype, one layer at a time: at the published
    widths a float32 construction of a few expert layers would not
    fit a 16 GB chip. `config` has `dtype`, `initializer_range` and
    the MLA widths."""

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

    def _attention(self, n):
        """`n` layers' attention weights and the two norms around
        them (`ln1` before the attention, `ln2` before the FFN that
        follows it)."""
        c = self.config
        h, heads = c.hidden_size, c.num_heads
        return {
            "ln1": self._ones("ln1", (n, h)),
            "wq_a": self._normal("wq_a", (n, h, c.q_lora_rank)),
            "q_norm": self._ones("q_norm", (n, c.q_lora_rank)),
            "wq_b": self._normal("wq_b", (n, c.q_lora_rank, heads * (
                c.qk_nope_head_dim + c.qk_rope_head_dim))),
            "wkv_a": self._normal("wkv_a", (n, h, c.latent_row)),
            "kv_norm": self._ones("kv_norm", (n, c.kv_lora_rank)),
            "wkv_b": self._normal("wkv_b", (n, c.kv_lora_rank, heads * (
                c.qk_nope_head_dim + c.v_head_dim))),
            "wo": self._normal("wo", (n, heads * c.v_head_dim, h)),
            "ln2": self._ones("ln2", (n, h)),
        }

    def _params_tree(self):
        return self._tree
