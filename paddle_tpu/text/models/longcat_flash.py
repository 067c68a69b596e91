"""LongCat-Flash (`meituan-longcat/LongCat-Flash-Chat`): double layers
with a shortcut-connected expert FFN (ScMoE), latent attention (MLA)
and zero-compute experts.

Published description: huggingface.co/meituan-longcat/
LongCat-Flash-Chat `config.json`. One of the `num_layers` "layers"
holds two attentions, two dense FFNs and ONE expert FFN whose output
joins the residual stream a sub-layer later (`N` RMSNorm with a
weight, no projection has a bias):

    h1 = x  + MLA_0(N(x))
    u1 = N(h1)
    m  = MoE(u1)                       # computed here, added at the end
    h2 = h1 + FFN_0(u1)                # dense SwiGLU, `ffn_hidden_size`
    h3 = h2 + MLA_1(N(h2))             # own weights, own cache rows
    x' = h3 + FFN_1(N(h3)) + m

- MLA: `text/models/mla.py`, which `glm4_moe_lite` shares, with the
  query times `(hidden_size / q_lora_rank)^0.5` (`mla_scale_q_lora`)
  and the normalised latent times `(hidden_size / kv_lora_rank)^0.5`
  (`mla_scale_kv_lora`) before `W_kvb`; the scaled latent is what a
  cache row holds.
- MoE: the router has `n_routed_experts + zero_expert_num` outputs;
  `s = softmax(u W_r)` over all of them, the `moe_topk` largest of
  `s + e_score_correction_bias` chosen, weights
  `routed_scaling_factor x s_i`, not renormalised
  (`moe.dropless.softmax_topk_route`). An id below
  `n_routed_experts` is a SwiGLU of width `expert_ffn_hidden_size`;
  one above is a zero-compute expert of type identity and adds
  `w_i * u`. No shared expert.
- A final `N` and an untied head.

The chip's share. `n_routed_experts` is the router's (the layer's)
count; this chip holds `experts_held` of them from `expert_first`
on (all of them by default) and computes their part of `m` for its
own tokens, plus its tokens' zero-compute picks; picks of experts
held elsewhere add nothing here (`dropless_expert_ffn(first=)`).

Assumed where the config is silent: the rotary pairing (`mla.py`);
`norm_topk_prob` false (the key is absent); an untied head.

The parameters are one stack of double layers with a leading layer
axis, run by one `lax.scan` (`layers`, which the serving runner and
`_k_forward` call with their own attention, twice a layer); the two
attentions and the two dense FFNs of a layer are two trees side by
side, so no weight is sliced out of a pair.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ...core.engine import apply_op
from ...incubate.distributed.models.moe.dropless import (
    dropless_expert_ffn, expert_counts, identity_expert_sum,
    softmax_topk_route)
from ...nn.layer.layers import Layer
from .common import SeededTree, embed, logits, swiglu
from .mla import attention_block, attention_params, forward

__all__ = ["LongcatFlashConfig", "LongcatFlashModel",
           "LongcatFlashForCausalLM"]


@dataclass(frozen=True)
class LongcatFlashConfig:
    """Published key names; `num_heads` and `max_seq_len` beside them
    are the names the serving engine reads of any model.
    `expert_first` / `experts_held`: the chip's share."""
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    initializer_range: float = 0.02
    dtype: str = "float32"
    expert_first: int = 0
    experts_held: int | None = None

    def __post_init__(self):
        if self.zero_expert_type != "identity":
            raise ValueError("zero-compute experts of type identity are "
                             "what LongCat-Flash publishes; got "
                             f"{self.zero_expert_type!r}")
        if self.experts_held is None:
            object.__setattr__(self, "experts_held",
                               self.n_routed_experts - self.expert_first)
        if not 0 <= self.expert_first < self.expert_first \
                + self.experts_held <= self.n_routed_experts:
            raise ValueError(
                f"experts {self.expert_first} .. {self.expert_first} + "
                f"{self.experts_held} of {self.n_routed_experts}")

    @property
    def num_heads(self):
        return self.num_attention_heads

    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def latent_row(self):
        """Values one token holds in a cache, per attention."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def mla_q_scale(self):
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1

    @property
    def mla_kv_scale(self):
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1


# -- the block (pure jnp; the serving runner reads `layers`) ---------------

def scmoe_ffn(u, mp, cfg, layer=None, live=None):
    """This chip's part of the expert FFN over tokens u [T, H]: the
    held experts' SwiGLUs and the tokens' zero-compute picks. `mp`
    is one layer's tree; with `layer` (a traced index) its experts
    `w13`, `w2` are instead the whole stack, read in place. Returns
    (out [T, H], {"moe_counts": tokens per held expert [E],
    "moe_picks": [top-k picks, picks of zero-compute experts]}, both
    over `live` rows)."""
    with jax.named_scope("moe/route"):
        idx, weights = softmax_topk_route(
            u, mp["router_w"], mp["router_b"], cfg.moe_topk,
            cfg.routed_scaling_factor)
        alive = jnp.ones(idx.shape[:1], bool) if live is None else live
        stats = {
            "moe_counts": expert_counts(idx - cfg.expert_first,
                                        cfg.experts_held, live),
            "moe_picks": jnp.stack([
                alive.sum() * cfg.moe_topk,
                ((idx >= cfg.n_routed_experts) & alive[:, None]).sum(),
            ]).astype(jnp.int32)}
    with jax.named_scope("moe/experts"):
        out = dropless_expert_ffn(u, idx, weights, mp["w13"], mp["w2"],
                                  layer, first=cfg.expert_first)
    with jax.named_scope("moe/zero"):
        out = out + identity_expert_sum(u, idx, weights,
                                        cfg.n_routed_experts)
    return out, stats


_EXPERTS = ("w13", "w2")     # read in place, never a scan's xs


def layers(params, x, carry, attend, window, scan, positions, live, cfg):
    """The stack of double layers over `x [T, hidden]` with the
    calling program's `attend` (`mla.attention_block`; `window`,
    `scan` and `positions` go unused, as in `glm4_moe_lite.layers`);
    an attention's number in the cache is `2 l` or `2 l + 1` for
    layer `l`. Returns (x, carry, the rows of every attention in
    cache order `[2 L, ...]` or None, the routing counts a layer)."""
    eps = cfg.rms_norm_eps
    stack = params["layers"]
    experts = {k: stack[k] for k in _EXPERTS}
    stack = {k: v for k, v in stack.items() if k not in _EXPERTS}
    n = stack["router_w"].shape[0]

    def double_layer(c, xs):
        lp, l = xs
        (ap0, ap1), (f0, f1) = lp["attn"], lp["ffn"]
        h, u, carry, row0 = attention_block(*c, ap0, 2 * l, attend, eps)
        m, stats = scmoe_ffn(u, {**lp, **experts}, cfg, layer=l,
                             live=live)
        h = h + swiglu(u, f0["w13"], f0["w2"])
        h, u, carry, row1 = attention_block(h, carry, ap1, 2 * l + 1,
                                            attend, eps)
        with jax.named_scope("scmoe/shortcut_add"):
            h = h + swiglu(u, f1["w13"], f1["w2"]) + m
        rows = None if row0 is None else jnp.stack([row0, row1])
        return (h, carry), (rows, stats)

    (x, carry), (rows, stats) = jax.lax.scan(
        double_layer, (x, carry), (stack, jnp.arange(n, dtype=jnp.int32)))
    if rows is not None:                 # [L, 2, ...] -> [2 L, ...]
        rows = rows.reshape((2 * n,) + rows.shape[2:])
    return x, carry, rows, stats


def _k_forward(ids, params, cfg):
    """Full causal forward through `layers` (`mla.forward`)."""
    return forward(layers, ids, params, cfg)


# -- the Layer ---------------------------------------------------------------

class LongcatFlashModel(SeededTree):
    """Decoder of `num_layers` double layers."""

    # what the serving runner reads (state_runner.StateRunner)
    decoder_layers = staticmethod(layers)
    embed = staticmethod(embed)
    logits = staticmethod(logits)

    def __init__(self, config: LongcatFlashConfig):
        super().__init__(config)
        c = config
        h, n = c.hidden_size, c.num_layers
        e, f = c.experts_held, c.expert_ffn_hidden_size
        outputs = c.n_routed_experts + c.zero_expert_num

        def ffn():
            return {"w13": self._normal("w13", (n, h, 2 * c.ffn_hidden_size)),
                    "w2": self._normal("w2", (n, c.ffn_hidden_size, h))}

        self._tree = {
            "embed": self._normal("embed", (c.vocab_size, h), layered=False),
            "head": self._normal("head", (h, c.vocab_size), layered=False),
            "norm_f": self._ones("norm_f", (h,)),
            "layers": {
                "attn": [attention_params(self, n),
                         attention_params(self, n)],
                "ffn": [ffn(), ffn()],
                # the router keeps its published width whatever is
                # held here; it and its selection bias stay float32.
                # The bias is drawn on the scale of the scores it is
                # added to (a uniform softmax score is 1 / outputs),
                # so that it steers the choice and does not make it:
                # at initializer_range (15 uniform scores at 768
                # outputs) every token picks the largest biases
                "router_w": self._normal("router_w", (n, h, outputs),
                                         dtype=jnp.float32),
                "router_b": self._normal("router_b", (n, outputs),
                                         dtype=jnp.float32,
                                         std=1.0 / outputs),
                "w13": self._normal("w13", (n, e, h, 2 * f)),
                "w2": self._normal("w2", (n, e, f, h)),
            },
        }

    @property
    def routed_experts(self):
        """(picks a token, experts held, hidden, an expert's width,
        the matrices' dtype): the grouped matmuls' static shape."""
        c = self.config
        return (c.moe_topk, c.experts_held, c.hidden_size,
                c.expert_ffn_hidden_size, self._dtype)

    @property
    def n_attentions(self):
        """Attentions that keep rows in a cache: two a layer."""
        return 2 * self.config.num_layers

    @property
    def latent_row(self):
        """A token's row in the cache an attention, key and value."""
        return self.config.latent_row

    def forward(self, input_ids):
        return apply_op("longcat_flash_forward", _k_forward, input_ids,
                        self._tree, cfg=self.config)


class LongcatFlashForCausalLM(Layer):
    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.model = LongcatFlashModel(config)
        self.config = config

    def forward(self, input_ids):
        return self.model(input_ids)
