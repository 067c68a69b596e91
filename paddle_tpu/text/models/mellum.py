"""Mellum 2 (`model_type` `mellum`): grouped-query attention whose
layers are of two kinds, three with a sliding window for every one
over the whole context, each kind with its own rotary rule, and
softmax-routed experts in every layer.

Published description: huggingface.co/JetBrains/Mellum2-12B-A2.5B-
Instruct `config.json`. `N` being RMSNorm (weight, no bias, eps
`rms_norm_eps`) and no projection having a bias, layer `l` is

    h = x + Attn_l(N(x))            x' = h + MoE_l(N(h))

and the logits `N(x) W_head`, the head untied.

- `Attn_l`: `num_attention_heads` query heads over
  `num_key_value_heads` K/V heads of `head_dim` (query head `h` reads
  K/V head `h // G`), `q` and `k` RMS-normalised per head (own gains)
  BEFORE rotary, scores `/ sqrt(head_dim)`, softmax, `W_o`. By
  `layer_types[l]`:
  `sliding_attention`: key `j` is visible to query `i` iff `i -
  sliding_window < j <= i` (`sliding_window` keys, itself included);
  rotary `default`: `inv_freq_i = theta^(-2i/D)`.
  `full_attention`: `j <= i`; rotary `yarn` (`rope_tables`):
  `inv_freq_i = theta^(-2i/D) ((1 - ramp_i) + ramp_i / factor)` with
  `ramp` rising from 0 at dimension `low` to 1 at `high` (the
  dimensions that turn `beta_fast` and `beta_slow` times over the
  original positions), and `cos`, `sin` times `attention_factor`.
  Neither table depends on the length served.
- `MoE_l`, in EVERY layer (`mlp_layer_types` is `sparse` throughout:
  the published `intermediate_size` 7168, a dense FFN's width, is
  used by no layer and the config keeps it unread): `s = softmax(u
  W_r)` over the experts in float32, the `num_experts_per_tok`
  largest chosen, weights `s_i / sum of the chosen` (`norm_topk_prob`),
  no selection bias, no shared expert (`dropless.topk_route`); expert
  `e` a SwiGLU of `moe_intermediate_size`; `dropless_expert_ffn`.

The layers are UNROLLED, each its own tree in `params["layers"]`, as
`lfm2_moe`'s; what an attention does with the cache is the calling
program's: `layers` is handed

    attend(q [T, Hq, D], k [T, Hkv*D], v [T, Hkv*D], carry, a)
        -> (attention output [T, Hq*D], carry)

with `a` the attention's number. What a serving cache has to know of
attention `a` is `attention_cache[a] = (group, index in the group,
window)`: layers are grouped BY KIND into groups of as many layers as
there are full-attention layers (2 of the 8 held in the benchmark's
cut, 7 of the published 28): group 0 the full layers, groups 1.. the
window layers in order. A cache block then holds one group's rows of
`block_size` tokens, and a window group's blocks stop being needed
once every token in them is `sliding_window` behind
(`inference/serving/kv_cache.py`).

Assumed where the config is silent: the per-head RMSNorm of q and k
(the config's other keys, `max_window_layers`, `use_sliding_window`,
`norm_topk_prob`, an explicit `head_dim`, are those of a lineage that
normalises so unconditionally); half-split rotary pairing; the norms'
gains `qk_norm_init` (1 by default; `lfm2_moe` has the reason a
benchmark draws them larger). Left out: the multi-token-prediction
head (the config carries no key for it).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ...core.engine import apply_op
from ...incubate.distributed.models.moe.dropless import (
    dropless_expert_ffn, expert_counts, topk_route)
from ...nn.layer.layers import Layer
from .mla import SeededTree, embed, rms_norm

__all__ = ["MellumConfig", "MellumModel", "MellumForCausalLM",
           "PUBLISHED_LAYER_TYPES", "rope_tables"]

# three sliding layers, then a full one, to the end
PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i % 4 == 3 else "sliding_attention"
    for i in range(28))


@dataclass(frozen=True)
class MellumConfig:
    """Published key names (the two rotary sections flattened:
    `rope_theta` is both kinds', the `yarn_*` keys the full layers');
    `num_layers`, `num_heads` and `max_seq_len` beside them are the
    names the serving engine reads of any model."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168        # published; no layer is dense
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0
    yarn_factor: float = 16.0
    yarn_original_max_position_embeddings: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    qk_norm_init: float = 1.0
    dtype: str = "float32"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {"sliding_attention", "full_attention"}:
            raise ValueError(
                f"{self.num_hidden_layers} layers of types "
                f"{self.layer_types}")
        n_full = self.count("full_attention")
        if not n_full or self.count("sliding_attention") % n_full:
            raise ValueError(
                f"{self.count('sliding_attention')} window layers do not "
                f"make whole cache groups of {n_full} (the full layers)")
        if self.tie_word_embeddings or not self.norm_topk_prob:
            raise ValueError("mellum as published: an untied head, "
                             "norm_topk_prob true")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2 or self.sliding_window < 1:
            raise ValueError(
                f"{self.num_attention_heads} query heads over "
                f"{self.num_key_value_heads} K/V heads of {self.head_dim}, "
                f"window {self.sliding_window}")

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
    def kv_row(self):
        """Values one token's keys (or values) are, per attention."""
        return self.num_key_value_heads * self.head_dim

    def count(self, kind):
        return sum(t == kind for t in self.layer_types)

    def window(self, kind):
        return self.sliding_window if kind == "sliding_attention" else None


# -- rotary, by layer type ---------------------------------------------------

def rope_tables(cfg, kind):
    """(inv_freq [D/2] float32, the factor on cos and sin) of a layer
    type, from the configuration alone (numpy: constants of a traced
    program). `sliding_attention`: the default rule. `full_attention`:
    YaRN. `dim(r) = D ln(original / (2 pi r)) / (2 ln theta)` is the
    dimension that turns `r` times over the original positions; below
    `low = floor(dim(beta_fast))` a frequency is kept, above `high =
    ceil(dim(beta_slow))` divided by `factor`, between them both by
    the ramp's share."""
    d = cfg.head_dim
    i = np.arange(d // 2, dtype=np.float64)
    inv = cfg.rope_theta ** (-2.0 * i / d)
    if kind == "sliding_attention":
        return inv.astype(np.float32), 1.0

    def dim(turns):
        return d * math.log(cfg.yarn_original_max_position_embeddings
                            / (2 * math.pi * turns)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim(cfg.yarn_beta_slow)), d - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = inv * ((1.0 - ramp) + ramp / cfg.yarn_factor)
    return inv.astype(np.float32), float(cfg.yarn_attention_factor)


def rotate(x, positions, inv_freq, factor):
    """Rotary embedding over the last dimension of `x [T, H, D]` at
    `positions [T]`, half-split pairing, `cos` and `sin` times
    `factor`."""
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


# -- the block (pure jnp; the serving runner reads `layers`) ---------------

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
    of 1024 columns cost 3: PERF.md, PR 38)."""
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


def attention_operator(u, carry, ap, a, kind, attend, positions, cfg):
    """Grouped-query attention over tokens u [T, H] at `positions`
    [T]: q and k normalised per head, then rotated by the layer
    type's table; the calling program attends and keeps the rows."""
    t, d = u.shape[0], cfg.head_dim
    hq, row = cfg.num_attention_heads, cfg.kv_row
    q, k, v = jnp.split(u @ ap["wqkv"], [hq * d, hq * d + row], axis=-1)
    q = rms_norm(q.reshape(t, hq, d), ap["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(k.reshape(t, -1, d), ap["k_norm"], cfg.rms_norm_eps)
    inv_freq, factor = rope_tables(cfg, kind)
    q = rotate(q, positions, inv_freq, factor)
    k = rotate(k, positions, inv_freq, factor).reshape(t, row)
    with jax.named_scope("gqa/attend/window" if cfg.window(kind)
                         else "gqa/attend/full"):
        out, carry = attend(q, k, v, carry, a)
    return out @ ap["wo"], carry


def moe_ffn(u, mp, cfg, live=None):
    """The routed experts over tokens u [T, H]. Returns (out, tokens
    per expert [E] over `live` rows)."""
    with jax.named_scope("moe/route"):
        idx, weights = topk_route(
            u, mp["router_w"], None, cfg.num_experts_per_tok, 1.0,
            jax.nn.softmax, True)
        counts = expert_counts(idx, cfg.num_experts, live)
    with jax.named_scope("moe/experts"):
        return dropless_expert_ffn(u, idx, weights, mp["w13"],
                                   mp["w2"]), counts


def layers(params, x, carry, attend, window, scan, positions, live, cfg):
    """Every layer over `x [T, hidden]`, unrolled, with the calling
    program's `attend` (module docstring; `window` and `scan`, what
    the runner offers a layer with per-slot state, go unused: this
    model has none). Returns (x, carry, {"moe_counts" [layers, E]})."""
    eps = cfg.rms_norm_eps
    counts = []
    for a, (kind, lp) in enumerate(zip(cfg.layer_types, params["layers"])):
        y, carry = attention_operator(
            rms_norm(x, lp["ln_attn"], eps), carry, lp["attn"], a, kind,
            attend, positions, cfg)
        x = x + y
        y, n = moe_ffn(rms_norm(x, lp["ln_ffn"], eps), lp["moe"], cfg, live)
        counts.append(n)
        x = x + y
    return x, carry, {"moe_counts": jnp.stack(counts)}


def logits(params, x, cfg):
    """Final norm and the untied head, float32."""
    x = rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
    return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


def _k_forward(ids, params, cfg):
    """Full causal forward, ids [B, S] -> logits [B, S, V] float32:
    what training and the tests run, through `layers`. Attention runs
    a sequence at a time, each layer with its own window; the experts
    see all B x S tokens as one list."""
    b, s = ids.shape

    def attend(q, k, v, carry, a):
        w = cfg.window(cfg.layer_types[a])
        out = jax.vmap(lambda q, k, v: attend_dense(q, k, v, window=w))(
            q.reshape((b, s) + q.shape[1:]), k.reshape(b, s, -1),
            v.reshape(b, s, -1))
        return out.reshape(b * s, -1), carry

    x = jnp.take(params["embed"], ids.reshape(b * s), axis=0)
    x, _, _ = layers(params, x, (), attend, None, None,
                     jnp.tile(jnp.arange(s), b), None, cfg)
    return logits(params, x, cfg).reshape(b, s, -1)


def cache_layout(cfg):
    """`(group, index in the group, window)` of every attention, in
    layer order: group 0 holds the full-attention layers, groups 1..
    the window layers, as many a group as there are full layers."""
    g = cfg.count("full_attention")
    n_full = n_win = 0
    out = []
    for kind in cfg.layer_types:
        if kind == "full_attention":
            out.append((0, n_full, None))
            n_full += 1
        else:
            out.append((1 + n_win // g, n_win % g, cfg.sliding_window))
            n_win += 1
    return tuple(out)


# -- the Layer ---------------------------------------------------------------

class MellumModel(SeededTree):
    """Decoder of `num_hidden_layers` layers, each its own tree."""

    # what the serving runner reads (state_runner.StateRunner)
    state_layers = staticmethod(layers)
    attend_dense = staticmethod(attend_dense)
    logits = staticmethod(logits)
    embed = staticmethod(embed)
    slot_state = ()

    def __init__(self, config: MellumConfig):
        super().__init__(config)
        c = config
        h, d = c.hidden_size, c.head_dim
        e, f = c.num_experts, c.moe_intermediate_size
        hq = c.num_attention_heads

        def gain(name):
            return self._add(name, jnp.full((d,), c.qk_norm_init,
                                            self._dtype))

        def layer():
            return {
                "ln_attn": self._ones("ln_attn", (h,)),
                "ln_ffn": self._ones("ln_ffn", (h,)),
                "attn": {
                    "wqkv": self._normal("wqkv", (h, hq * d + 2 * c.kv_row),
                                         layered=False),
                    "q_norm": gain("q_norm"), "k_norm": gain("k_norm"),
                    "wo": self._normal("wo", (hq * d, h), layered=False)},
                "moe": {
                    # the router stays float32
                    "router_w": self._normal("router_w", (h, e),
                                             layered=False,
                                             dtype=jnp.float32),
                    # an expert at a time: a layer's 64 never exist in
                    # float32 as a whole
                    "w13": self._normal("w13", (e, h, 2 * f)),
                    "w2": self._normal("w2", (e, f, h))}}

        self._tree = {
            "embed": self._normal("embed", (c.vocab_size, h), layered=False),
            "norm_f": self._ones("norm_f", (h,)),
            "layers": [layer() for _ in c.layer_types],
            "head": self._normal("head", (h, c.vocab_size), layered=False),
        }

    @property
    def routed_experts(self):
        """(picks a token, experts held, hidden, an expert's width,
        the matrices' dtype): the grouped matmuls' static shape."""
        c = self.config
        return (c.num_experts_per_tok, c.num_experts, c.hidden_size,
                c.moe_intermediate_size, self._dtype)

    @property
    def n_attentions(self):
        return self.config.num_hidden_layers

    @property
    def kv_heads(self):
        """(query heads, K/V heads, head_dim)."""
        c = self.config
        return c.num_attention_heads, c.num_key_value_heads, c.head_dim

    @property
    def attention_cache(self):
        """What a cache has to know of each attention
        (`cache_layout`)."""
        return cache_layout(self.config)

    def forward(self, input_ids):
        return apply_op("mellum_forward", _k_forward, input_ids,
                        self._tree, cfg=self.config)


class MellumForCausalLM(Layer):
    def __init__(self, config: MellumConfig):
        super().__init__()
        self.model = MellumModel(config)
        self.config = config

    def forward(self, input_ids):
        return self.model(input_ids)
