"""GLM-4.7-Flash (`model_type` `glm4_moe_lite`): latent attention (MLA)
and sigmoid-routed experts with a shared expert.

Published description: huggingface.co/zai-org/GLM-4.7-Flash
`config.json`. The block, `N` being RMSNorm (weight, no bias) and
every projection without bias:

    h  = x + MLA(N(x))          x' = h + FFN(N(h))

- MLA: `text/models/mla.py`, which `longcat_flash` shares, with both
  of its scale constants 1.
- FFN: the first `first_k_dense_replace` layers are SwiGLU of width
  `intermediate_size`; the others route each token to
  `num_experts_per_tok` of `n_routed_experts` SwiGLUs of width
  `moe_intermediate_size` (`incubate...moe.dropless`) and add the
  shared expert's.

Assumed where the config is silent: the rotary pairing (`mla.py`).
The multi-token-prediction module (`num_nextn_predict_layers`) is a
drafter and no part of the next-token forward pass: not built.

The two kinds of layer have different trees, so the parameters are
two stacks with a leading layer axis (`dense`, `moe`), each run by
one `lax.scan` (`layers`, which the serving runner and `_k_forward`
call with their own attention). Weights are drawn on the device
(`common.SeededTree`): at the published widths one expert layer is
635 M parameters.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ...core.engine import apply_op
from ...incubate.distributed.models.moe.dropless import (
    dropless_expert_ffn, expert_counts, sigmoid_topk_route)
from ...nn.layer.layers import Layer
from .common import SeededTree, embed, logits, swiglu
from .mla import attention_block, attention_params, forward

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

    mla_q_scale = mla_kv_scale = 1      # class constants, no fields


# -- the block (pure jnp; the serving runner reads `layers`) ---------------

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


_EXPERTS = ("w13", "w2")     # read in place, never a scan's xs


def layers(params, x, carry, attend, window, scan, positions, live, cfg):
    """Both stacks over `x [T, hidden]` with the calling program's
    `attend` (`mla.attention_block`; `window`, `scan` and `positions`,
    what the runner offers every model, go unused: `attend` has the
    positions); an attention's number in the cache is its layer's.
    The routed experts' stacked weights are not among a scan's `xs`
    (slicing a layer out would copy all its experts): `moe_ffn` reads
    them as `[L*E, ...]` groups. Returns (x, carry, the rows of every
    attention in cache order or None, {"moe_counts"})."""
    eps = cfg.rms_norm_eps
    dense, moe = params["dense"], params["moe"]
    experts = {k: moe[k] for k in _EXPERTS}
    moe = {k: v for k, v in moe.items() if k not in _EXPERTS}
    n_dense = jax.tree_util.tree_leaves(dense)[0].shape[0]
    n_moe = jax.tree_util.tree_leaves(moe)[0].shape[0]

    def dense_layer(c, xs):
        lp, layer = xs
        h, u, carry, ys = attention_block(*c, lp["attn"], layer, attend,
                                          eps)
        return (h + swiglu(u, lp["w13"], lp["w2"]), carry), ys

    def moe_layer(c, xs):
        lp, layer = xs
        h, u, carry, ys = attention_block(*c, lp["attn"], n_dense + layer,
                                          attend, eps)
        out, counts = moe_ffn(u, {**lp, **experts}, cfg, layer=layer,
                              live=live)
        return (h + out, carry), (ys, counts)

    (x, carry), ys_d = jax.lax.scan(
        dense_layer, (x, carry),
        (dense, jnp.arange(n_dense, dtype=jnp.int32)))
    (x, carry), (ys_m, counts) = jax.lax.scan(
        moe_layer, (x, carry),
        (moe, jnp.arange(n_moe, dtype=jnp.int32)))
    rows = None if ys_m is None else jnp.concatenate([ys_d, ys_m], 0)
    return x, carry, rows, {"moe_counts": counts}


def _k_forward(ids, params, cfg):
    """Full causal forward through `layers` (`mla.forward`)."""
    return forward(layers, ids, params, cfg)


# -- the Layer ---------------------------------------------------------------

class Glm4MoeLiteModel(SeededTree):
    """Decoder with two stacks of layers: `first_k_dense_replace`
    dense ones, then the expert layers."""

    # what the serving runner reads (state_runner.StateRunner)
    decoder_layers = staticmethod(layers)
    embed = staticmethod(embed)
    logits = staticmethod(logits)

    def __init__(self, config: Glm4MoeLiteConfig):
        super().__init__(config)
        c = config
        if c.n_shared_experts != 1:
            raise ValueError("one shared expert is what glm4_moe_lite "
                             f"publishes; got {c.n_shared_experts}")
        h = c.hidden_size
        n_dense = c.first_k_dense_replace
        n_moe = c.num_hidden_layers - n_dense
        e, f = c.n_routed_experts, c.moe_intermediate_size
        self._tree = {
            "embed": self._normal("embed", (c.vocab_size, h), layered=False),
            "head": self._normal("head", (h, c.vocab_size), layered=False),
            "norm_f": self._ones("norm_f", (h,)),
            "dense": {
                "attn": attention_params(self, n_dense),
                "w13": self._normal("w13", (n_dense, h,
                                            2 * c.intermediate_size)),
                "w2": self._normal("w2", (n_dense, c.intermediate_size, h)),
            },
            "moe": {
                "attn": attention_params(self, n_moe),
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

    @property
    def routed_experts(self):
        """(picks a token, experts held, hidden, an expert's width,
        the matrices' dtype): the grouped matmuls' static shape."""
        c = self.config
        return (c.num_experts_per_tok, c.n_routed_experts, c.hidden_size,
                c.moe_intermediate_size, self._dtype)

    @property
    def n_attentions(self):
        """Attentions that keep rows in a cache: one a layer."""
        return self.config.num_hidden_layers

    @property
    def latent_row(self):
        """A token's row in the cache an attention, key and value."""
        return self.config.latent_row

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
