"""LFM2-24B-A2B (`model_type` `lfm2_moe`): gated short convolutions
where most models attend, grouped-query attention in every fourth
layer, sigmoid-routed experts.

Published description: huggingface.co/LiquidAI/LFM2-24B-A2B
`config.json`. `N` being RMSNorm (weight, no bias, eps `norm_eps`)
and no projection having a bias, layer `i` is

    h  = x + Op_i(N_op(x))          x' = h + FFN_i(N_ffn(h))

with `Op_i` by `layer_types[i]`:

- `conv`, the gated short convolution (`conv_L_cache` L taps):
  `[B | C | X] = u W_in`, `z = B * X`, `c_t = sum_j w[j] *
  z_{t-(L-1)+j}` (depthwise, causal, `z` zero before position 0),
  `y = (C * c) W_out`. What a sequence has to keep of a layer is the
  last `L - 1` rows of `z` and nothing else: a state of FIXED size,
  which no block table addresses.
- `full_attention`: `num_attention_heads` query heads over
  `num_key_value_heads` K/V heads (query head `h` reads K/V head
  `h // G`), `q` and `k` RMS-normalised per head (own gains) BEFORE
  rotary (whole head, theta `rope_theta`, half-split pairing),
  scores `/ sqrt(head_dim)`, causal softmax, `W_o`.

and `FFN_i` a dense SwiGLU of `intermediate_size` for `i <
num_dense_layers`, else `num_experts_per_tok` of `num_experts`
SwiGLUs of `moe_intermediate_size` by `dropless.sigmoid_topk_route`
(sigmoid scores in float32, chosen by score + bias, renormalised,
times `routed_scaling_factor`; its `1e-20` under the sum where the
published code has `1e-6`: 4 ulps of a float32 sum near 2) and
`dropless_expert_ffn`; no shared expert. Logits are `N(x) E^T`, the
head tied to the embedding (`tie_word_embeddings`; untied, a `head`
leaf).

The layers are UNROLLED: each is its own tree in `params["layers"]`
(a list), so no stack is sliced (slicing a layer out of a stack
copies it: PR 27) and no `lax.switch` chooses between kinds. What a
layer does with the cache is the calling program's: `layers` is
handed

    attend(q [T, Hq, D], k [T, Hkv*D], v [T, Hkv*D], carry, a)
        -> (attention output [T, Hq*D], carry)
    window(z [T, H], carry, c) -> (z_{t-L+1} .. z_t [T, L, H], carry)

with `a` / `c` the attention's / convolution's number among its
kind. The serving runner (`inference/serving/state_runner.py`)
writes K/V rows through block tables and keeps the windows' tails
in per-slot state; `_k_forward` (training, tests) attends densely
and shifts the sequence.

Assumed where the config is silent: `head_dim = hidden_size /
num_attention_heads`; the split order `B, C, X` and the rotary
pairing (column permutations of seeded weights); the taps' scale
(`conv_init_std`: 3^-0.5, at which the three taps keep `z`'s
variance, PyTorch's default for a depthwise Conv1d of 3 taps; at
`initializer_range` a convolution would add a fiftieth of what an
FFN adds to the residual stream); the q/k norms' gains
(`qk_norm_init`, 1 as every norm's by default: with seeded
projections of unit RMS the scores then have a standard deviation
of 1, attention is a near-uniform average over its context, and a
benchmark's check cannot tell a wrong block from rounding; its
configuration draws them at 2). The taps are stored `[L, H]` (lanes
on the channels), the published `[H, 1, L]` transposed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ...core.engine import apply_op
from ...incubate.distributed.models.moe.dropless import (
    dropless_expert_ffn, expert_counts, sigmoid_topk_route)
from ...nn.layer.layers import Layer
from .mla import SeededTree, embed, rms_norm, rotate, swiglu

__all__ = ["Lfm2MoeConfig", "Lfm2MoeModel", "Lfm2MoeForCausalLM",
           "PUBLISHED_LAYER_TYPES"]

# conv conv attn, then (conv conv conv attn) to the end
PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i >= 2 and (i - 2) % 4 == 0 else "conv"
    for i in range(40))


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """Published key names; `num_layers`, `num_heads` and
    `max_seq_len` beside them are the names the serving engine reads
    of any model."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    conv_init_std: float = 3 ** -0.5
    qk_norm_init: float = 1.0
    dtype: str = "float32"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(
                f"{self.num_hidden_layers} layers of types "
                f"{self.layer_types}")
        if self.conv_bias or not (self.norm_topk_prob
                                  and self.use_expert_bias):
            raise ValueError(
                "lfm2_moe as published: conv_bias false, norm_topk_prob "
                "and use_expert_bias true")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads over "
                f"{self.num_key_value_heads} K/V heads at hidden "
                f"{self.hidden_size}")

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
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_row(self):
        """Values one token's keys (or values) are, per attention."""
        return self.num_key_value_heads * self.head_dim

    def count(self, kind):
        return sum(t == kind for t in self.layer_types)


# -- the block (pure jnp; the serving runner reads `layers`) ---------------

def attend_dense(q, k, v, q_block=512):
    """Causal attention of S tokens over themselves, grouped heads:
    q [S, Hq, D], k / v [S, Hkv*D] -> [S, Hq*D]. Queries in blocks
    (the largest power of two under `q_block` that divides S), so
    that 32 heads x 2048^2 float32 scores never exist at once."""
    s, hq, d = q.shape
    hkv = k.shape[-1] // d
    k, v = k.reshape(s, hkv, d), v.reshape(s, hkv, d)
    qg = q.reshape(s, hkv, hq // hkv, d)
    qb = math.gcd(s, 1 << (max(1, q_block).bit_length() - 1))

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i, qb)
        scores = jnp.einsum("qkgd,skd->kgqs", qi, k,
                            preferred_element_type=jnp.float32)
        seen = (i + jnp.arange(qb))[:, None] >= jnp.arange(s)
        probs = jax.nn.softmax(
            jnp.where(seen, scores / math.sqrt(d), -1e30), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", probs.astype(v.dtype), v)

    out = block(0) if qb == s else jax.lax.map(block, jnp.arange(0, s, qb))
    return out.reshape(s, hq * d)


def attention_operator(u, carry, ap, a, attend, positions, cfg):
    """Grouped-query attention over tokens u [T, H] at `positions`
    [T]: q and k normalised per head, then rotated; the calling
    program attends and keeps the rows."""
    t, d = u.shape[0], cfg.head_dim
    hq, row = cfg.num_attention_heads, cfg.kv_row
    q, k, v = jnp.split(u @ ap["wqkv"], [hq * d, hq * d + row], axis=-1)
    q = rms_norm(q.reshape(t, hq, d), ap["q_norm"], cfg.norm_eps)
    k = rms_norm(k.reshape(t, -1, d), ap["k_norm"], cfg.norm_eps)
    q = rotate(q, positions[:, None], cfg.rope_theta)
    k = rotate(k, positions[:, None], cfg.rope_theta).reshape(t, row)
    with jax.named_scope("gqa/attend"):
        out, carry = attend(q, k, v, carry, a)
    return out @ ap["wo"], carry


def conv_operator(u, carry, cp, c, window, cfg):
    """The gated short convolution over tokens u [T, H]; the calling
    program says what precedes each token (`window`)."""
    with jax.named_scope("lfm2/conv"):
        gate_b, gate_c, x = jnp.split(u @ cp["w_in"], 3, axis=-1)
        win, carry = window(gate_b * x, carry, c)        # [T, L, H]
        conv = (win.astype(jnp.float32)
                * cp["taps"].astype(jnp.float32)).sum(1).astype(u.dtype)
        return (gate_c * conv) @ cp["w_out"], carry


def moe_ffn(u, mp, cfg, live=None):
    """The routed experts over tokens u [T, H]. Returns (out, tokens
    per expert [E] over `live` rows)."""
    with jax.named_scope("moe/route"):
        idx, weights = sigmoid_topk_route(
            u, mp["router_w"], mp["router_b"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)
        counts = expert_counts(idx, cfg.num_experts, live)
    with jax.named_scope("moe/experts"):
        return dropless_expert_ffn(u, idx, weights, mp["w13"],
                                   mp["w2"]), counts


def layers(params, x, carry, attend, window, scan, positions, live, cfg):
    """Every layer over `x [T, hidden]`, unrolled, with the calling
    program's `attend` and `window` (module docstring; `scan`, what the
    runner offers a state-space layer, goes unused). Returns (x,
    carry, {"moe_counts" [expert layers, E]})."""
    eps = cfg.norm_eps
    a = c = 0
    counts = []
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        u = rms_norm(x, lp["ln_op"], eps)
        if kind == "conv":
            y, carry = conv_operator(u, carry, lp["conv"], c, window, cfg)
            c += 1
        else:
            y, carry = attention_operator(u, carry, lp["attn"], a, attend,
                                          positions, cfg)
            a += 1
        x = x + y
        u = rms_norm(x, lp["ln_ffn"], eps)
        if "moe" in lp:
            y, n = moe_ffn(u, lp["moe"], cfg, live)
            counts.append(n)
        else:
            y = swiglu(u, lp["ffn"]["w13"], lp["ffn"]["w2"])
        x = x + y
    return x, carry, {"moe_counts": jnp.stack(counts)} if counts else {}


def logits(params, x, cfg):
    """Final norm and the head (the embedding's transpose when
    tied), float32."""
    x = rms_norm(x, params["norm_f"], cfg.norm_eps)
    if "head" in params:
        return jnp.dot(x, params["head"],
                       preferred_element_type=jnp.float32)
    return jnp.einsum("...h,vh->...v", x, params["embed"],
                      preferred_element_type=jnp.float32)


def _k_forward(ids, params, cfg):
    """Full causal forward, ids [B, S] -> logits [B, S, V] float32:
    what training and the tests run, through `layers`. Attention and
    the convolutions run a sequence at a time; the FFNs see all
    B x S tokens as one list."""
    b, s = ids.shape
    n = cfg.conv_L_cache - 1

    def attend(q, k, v, carry, a):
        out = jax.vmap(attend_dense)(
            q.reshape((b, s) + q.shape[1:]), k.reshape(b, s, -1),
            v.reshape(b, s, -1))
        return out.reshape(b * s, -1), carry

    def window(z, carry, c):
        zp = jnp.pad(z.reshape(b, s, -1), ((0, 0), (n, 0), (0, 0)))
        win = jnp.stack([zp[:, j:j + s] for j in range(n + 1)], 2)
        return win.reshape((b * s,) + win.shape[2:]), carry

    x = jnp.take(params["embed"], ids.reshape(b * s), axis=0)
    x, _, _ = layers(params, x, (), attend, window, None,
                     jnp.tile(jnp.arange(s), b), None, cfg)
    return logits(params, x, cfg).reshape(b, s, -1)


# -- the Layer ---------------------------------------------------------------

class Lfm2MoeModel(SeededTree):
    """Decoder of `num_hidden_layers` layers, each its own tree."""

    # what the serving runner reads (state_runner.StateRunner)
    state_layers = staticmethod(layers)
    attend_dense = staticmethod(attend_dense)
    logits = staticmethod(logits)
    embed = staticmethod(embed)

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__(config)
        c = config
        h, d = c.hidden_size, c.head_dim
        e, f = c.num_experts, c.moe_intermediate_size

        def operator(kind):
            if kind == "conv":
                return {"conv": {
                    "w_in": self._normal("w_in", (h, 3 * h), layered=False),
                    "taps": self._normal("taps", (c.conv_L_cache, h),
                                         layered=False,
                                         std=c.conv_init_std),
                    "w_out": self._normal("w_out", (h, h), layered=False),
                }}
            return {"attn": {
                "wqkv": self._normal(
                    "wqkv", (h, c.num_attention_heads * d + 2 * c.kv_row),
                    layered=False),
                "q_norm": self._gain("q_norm", (d,), c.qk_norm_init),
                "k_norm": self._gain("k_norm", (d,), c.qk_norm_init),
                "wo": self._normal("wo", (c.num_attention_heads * d, h),
                                   layered=False),
            }}

        def ffn(i):
            if i < c.num_dense_layers:
                return {"ffn": {
                    "w13": self._normal("w13", (h, 2 * c.intermediate_size),
                                        layered=False),
                    "w2": self._normal("w2", (c.intermediate_size, h),
                                       layered=False)}}
            return {"moe": {
                # the router and its selection bias stay float32
                "router_w": self._normal("router_w", (h, e), layered=False,
                                         dtype=jnp.float32),
                "router_b": self._normal("router_b", (e,), layered=False,
                                         dtype=jnp.float32),
                # an expert at a time: a layer's 64 never exist in
                # float32 as a whole
                "w13": self._normal("w13", (e, h, 2 * f)),
                "w2": self._normal("w2", (e, f, h))}}

        self._tree = {
            "embed": self._normal("embed", (c.vocab_size, h), layered=False),
            "norm_f": self._ones("norm_f", (h,)),
            "layers": [
                {"ln_op": self._ones("ln_op", (h,)),
                 "ln_ffn": self._ones("ln_ffn", (h,)),
                 **operator(kind), **ffn(i)}
                for i, kind in enumerate(c.layer_types)],
        }
        if not c.tie_word_embeddings:
            self._tree["head"] = self._normal("head", (h, c.vocab_size),
                                              layered=False)

    def _gain(self, name, shape, value):
        return self._add(name, jnp.full(shape, value, self._dtype))

    @property
    def routed_experts(self):
        """(picks a token, experts held, hidden, an expert's width,
        the matrices' dtype): the grouped matmuls' static shape."""
        c = self.config
        return (c.num_experts_per_tok, c.num_experts, c.hidden_size,
                c.moe_intermediate_size, self._dtype)

    @property
    def n_attentions(self):
        """Attentions that keep K/V rows in a cache."""
        return self.config.count("full_attention")

    @property
    def kv_heads(self):
        """(query heads, K/V heads, head_dim)."""
        c = self.config
        return c.num_attention_heads, c.num_key_value_heads, c.head_dim

    @property
    def slot_state(self):
        """What a sequence keeps beside its K/V rows, as `(kind,
        (layers, *shape a layer), dtype)` (None: the cache's): the
        last L - 1 gated inputs of every convolution."""
        c = self.config
        return (("window", (c.count("conv"), c.conv_L_cache - 1,
                            c.hidden_size), None),)

    def forward(self, input_ids):
        return apply_op("lfm2_moe_forward", _k_forward, input_ids,
                        self._tree, cfg=self.config)


class Lfm2MoeForCausalLM(Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.model = Lfm2MoeModel(config)
        self.config = config

    def forward(self, input_ids):
        return self.model(input_ids)
