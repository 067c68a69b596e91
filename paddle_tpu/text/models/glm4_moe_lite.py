"""GLM-4.7-Flash (`model_type` `glm4_moe_lite`): latent attention (MLA)
and sigmoid-routed experts with a shared expert.

Published description: huggingface.co/zai-org/GLM-4.7-Flash
`config.json`. The block, `N` being RMSNorm (weight, no bias) and
every projection without bias:

    h  = x + MLA(N(x))          x' = h + FFN(N(h))

- MLA: `c_q = N(u W_qa)`, `q = c_q W_qb` per head `[nope | rope]`;
  `[c_kv | k_r] = u W_kva`, `c_kv = N(c_kv)`; rotary (theta
  `rope_theta`, no scaling) over the rope dims of `q` and over `k_r`,
  which all heads share; `[k_nope | v] = c_kv W_kvb` per head; causal
  softmax of `[q_nope | q_rope] . [k_nope | k_rope] / sqrt(nope +
  rope)`; heads concatenated, times `W_o`. What a cache has to hold
  of a token is `[c_kv | k_rope]` alone (`kv_lora_rank +
  qk_rope_head_dim` values a layer): `mla_latent` makes that row,
  `mla_attend_dense` expands it through `W_kvb` (prefill, training),
  `mla_attend_absorbed` folds `W_kvb` into the query and the output
  and reads nothing but the rows (decode).
- FFN: the first `first_k_dense_replace` layers are SwiGLU of width
  `intermediate_size`; the others route each token to
  `num_experts_per_tok` of `n_routed_experts` SwiGLUs of width
  `moe_intermediate_size` (`incubate...moe.dropless`) and add the
  shared expert's.

Assumed where the config is silent: the rotary pairing is half-split
(dims `i` and `i + rope/2` rotate together); with seeded weights the
interleaved pairing is a column permutation of `W_qb` and `W_kva`.
The multi-token-prediction module (`num_nextn_predict_layers`) is a
drafter and no part of the next-token forward pass: not built.

The two kinds of layer have different trees, so the parameters are
two stacks with a leading layer axis (`dense`, `moe`), each run by
one `lax.scan`. Weights are drawn on the device, in the configured
dtype, one layer at a time: at the published widths one expert layer
is 635 M parameters, and a float32 construction of seven would not
fit a 16 GB chip.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ...core.engine import apply_op
from ...core.tensor import Parameter
from ...incubate.distributed.models.moe.dropless import (
    dropless_expert_ffn, expert_counts, sigmoid_topk_route)
from ...nn.layer.layers import Layer
from ...ops import random as _random

__all__ = ["Glm4MoeLiteConfig", "Glm4MoeLiteModel",
           "Glm4MoeLiteForCausalLM"]


@dataclass(frozen=True)
class Glm4MoeLiteConfig:
    """Published key names; `num_layers`, `num_heads` and
    `max_seq_len` beside them are the names the serving engine reads
    of any model."""
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    first_k_dense_replace: int = 1
    max_position_embeddings: int = 202752
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def num_heads(self):
        return self.num_attention_heads

    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def latent_row(self):
        """Values one token holds in a cache, per layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim


# -- the block's mathematics (pure jnp; the serving runner reads them) -----

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


def swiglu(u, w13, w2):
    gate, up = jnp.split(u @ w13, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w2


def mla_query(u, ap, cfg, positions):
    """(q_nope [..., H, nope], q_rope [..., H, rope], rotated)."""
    c_q = rms_norm(u @ ap["wq_a"], ap["q_norm"], cfg.rms_norm_eps)
    q = (c_q @ ap["wq_b"]).reshape(
        u.shape[:-1] + (cfg.num_heads,
                        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    return q_nope, rotate(q_rope, positions[..., None], cfg.rope_theta)


def mla_latent(u, ap, cfg, positions):
    """The row a cache holds of each token: `[N(c_kv) | R(k_r)]`,
    `[..., kv_lora_rank + qk_rope_head_dim]`."""
    c_kv, k_r = jnp.split(u @ ap["wkv_a"], [cfg.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, ap["kv_norm"], cfg.rms_norm_eps)
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


def mla_attend_absorbed(q_nope, q_rope, ctx, lens, ap, cfg):
    """Attention of one query token a sequence over cached latent
    rows, W_kvb absorbed: `q_lat = q_nope W^K^T`, scores over the
    rows as they are, `o = (P c_kv) W^V`. q_* [B, H, .], ctx
    [B, T, row] (positions >= lens[b] masked) -> [B, H * v_head_dim].
    The same mathematics as `mla_attend_dense`."""
    wk, wv = _wkv_b(ap, cfg)
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, wk)
    q = jnp.concatenate([q_lat, q_rope], -1)            # [B, H, row]
    # a cache may store its rows wider than they are (zero-padded to
    # a multiple of the device's lanes): zeros against zeros
    q = jnp.pad(q, ((0, 0), (0, 0), (0, ctx.shape[-1] - q.shape[-1])))
    scores = jnp.einsum("bhr,btr->bht", q, ctx,
                        preferred_element_type=jnp.float32)
    live = jnp.arange(ctx.shape[1])[None, :] < lens[:, None]
    probs = jax.nn.softmax(
        jnp.where(live[:, None, :], scores * _sm_scale(cfg), -1e30),
        axis=-1)
    o_lat = jnp.einsum("bht,btr->bhr", probs.astype(ctx.dtype),
                       ctx)[..., :cfg.kv_lora_rank]
    out = jnp.einsum("bhc,chd->bhd", o_lat, wv)
    return out.reshape(out.shape[0], -1)


def moe_ffn(u, mp, cfg, layer=None, live=None):
    """Routed experts plus the shared expert over tokens u [T, H].
    `mp` is one layer's tree; with `layer` (a traced index) its
    routed experts `w13`, `w2` are instead the whole stack, of which
    the grouped matmul reads that layer's without slicing it out.
    Returns (out [T, H], tokens per expert [E] over `live` rows)."""
    with jax.named_scope("moe/route"):
        idx, weights = sigmoid_topk_route(
            u, mp["router_w"], mp["router_b"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)
        counts = expert_counts(idx, cfg.n_routed_experts, live)
    with jax.named_scope("moe/experts"):
        out = dropless_expert_ffn(u, idx, weights, mp["w13"], mp["w2"],
                                  layer)
    with jax.named_scope("moe/shared"):
        out = out + swiglu(u, mp["shared_w13"], mp["shared_w2"])
    return out, counts


def _k_forward(ids, params, cfg):
    """Full causal forward, ids [B, S] -> logits [B, S, V] float32:
    what training and the tests run, through the same functions the
    serving programs use. Attention runs a sequence at a time
    (vmap); the FFNs see all B x S tokens as one list."""
    eps = cfg.rms_norm_eps
    b, s = ids.shape
    positions = jnp.arange(s)

    def attend_one(x, ap):
        u = rms_norm(x, ap["ln1"], eps)
        q_nope, q_rope = mla_query(u, ap, cfg, positions)
        latent = mla_latent(u, ap, cfg, positions)
        return mla_attend_dense(q_nope, q_rope, latent, ap, cfg)

    def attend(x, ap):
        attn = jax.vmap(attend_one, in_axes=(0, None))(x, ap)
        h = x + attn @ ap["wo"]
        return h, rms_norm(h, ap["ln2"], eps).reshape(b * s, -1)

    def dense(x, lp):
        h, u = attend(x, lp["attn"])
        return h + swiglu(u, lp["w13"], lp["w2"]).reshape(h.shape), None

    def moe(x, lp):
        h, u = attend(x, lp["attn"])
        return h + moe_ffn(u, lp, cfg)[0].reshape(h.shape), None

    x = jnp.take(params["embed"], ids, axis=0)
    x, _ = jax.lax.scan(dense, x, params["dense"])
    x, _ = jax.lax.scan(moe, x, params["moe"])
    x = rms_norm(x, params["norm_f"], eps)
    return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


# -- the Layer ---------------------------------------------------------------

class Glm4MoeLiteModel(Layer):
    """Decoder with two stacks of layers: `first_k_dense_replace`
    dense ones, then the expert layers."""

    def __init__(self, config: Glm4MoeLiteConfig):
        super().__init__()
        self.config = c = config
        if c.n_shared_experts != 1:
            raise ValueError("one shared expert is what glm4_moe_lite "
                             f"publishes; got {c.n_shared_experts}")
        self._dtype = jnp.dtype(c.dtype)
        self._key = _random.next_key()
        self._n_leaf = 0
        h, heads = c.hidden_size, c.num_attention_heads
        n_dense = c.first_k_dense_replace
        n_moe = c.num_hidden_layers - n_dense
        e, f = c.n_routed_experts, c.moe_intermediate_size

        def attn(n):
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

        self._tree = {
            "embed": self._normal("embed", (c.vocab_size, h), layered=False),
            "head": self._normal("head", (h, c.vocab_size), layered=False),
            "norm_f": self._ones("norm_f", (h,)),
            "dense": {
                "attn": attn(n_dense),
                "w13": self._normal("w13", (n_dense, h,
                                            2 * c.intermediate_size)),
                "w2": self._normal("w2", (n_dense, c.intermediate_size, h)),
            },
            "moe": {
                "attn": attn(n_moe),
                # the router and its selection bias stay float32
                "router_w": self._normal("router_w", (n_moe, h, e),
                                         dtype=jnp.float32),
                "router_b": self._normal("router_b", (n_moe, e),
                                         dtype=jnp.float32),
                "w13": self._normal("w13", (n_moe, e, h, 2 * f)),
                "w2": self._normal("w2", (n_moe, e, f, h)),
                "shared_w13": self._normal("shared_w13", (n_moe, h, 2 * f)),
                "shared_w2": self._normal("shared_w2", (n_moe, f, h)),
            },
        }

    def _add(self, name, value):
        self._n_leaf += 1
        p = Parameter(value, name=f"{name}_{self._n_leaf}")
        self.add_parameter(f"{name}_{self._n_leaf}", p)
        return p

    def _ones(self, name, shape):
        return self._add(name, jnp.ones(shape, self._dtype))

    def _normal(self, name, shape, layered=True, dtype=None):
        """initializer_range x normal, drawn on the device in the
        target dtype, one slice of the leading (layer) axis at a
        time: a leaf never exists in float32 as a whole."""
        dtype = dtype or self._dtype
        std = self.config.initializer_range
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

    def forward(self, input_ids):
        return apply_op("glm4_moe_lite_forward", _k_forward, input_ids,
                        self._tree, cfg=self.config)


class Glm4MoeLiteForCausalLM(Layer):
    def __init__(self, config: Glm4MoeLiteConfig):
        super().__init__()
        self.model = Glm4MoeLiteModel(config)
        self.config = config

    def forward(self, input_ids):
        return self.model(input_ids)
