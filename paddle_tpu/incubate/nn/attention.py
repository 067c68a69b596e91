"""Fused scaled-dot-product attention.

Parity target: the reference's fused_attention CUDA stack
(paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h).

TPU-native design: a Pallas flash-attention kernel (attention_pallas.py)
for the TPU hot path — tiled over (block_q, block_kv) with online
softmax so the [S, S] score matrix never hits HBM — with an XLA
fallback that relies on compiler fusion (still strong on TPU for
moderate sequence lengths). Selection is automatic by platform.
"""
from __future__ import annotations


import numpy as np
import jax
import jax.numpy as jnp

from ...core.engine import apply_op
from ...core.tensor import Tensor
from ...ops import random as _random


def _xla_attention(q, k, v, mask, scale, causal, dropout_p, key):
    # q,k,v: [B, H, Sq/Skv, D]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * scale
    if mask is not None:
        logits = logits + mask.astype(logits.dtype)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    probs = probs.astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _use_pallas(q_shape, dtype, has_mask, dropout_p):
    if jax.devices()[0].platform != "tpu":
        return False
    if dropout_p > 0.0 or has_mask:
        return False  # pallas kernel currently covers causal/full paths
    b, h, s, d = q_shape
    return s >= 128 and d in (64, 128, 256) and s % 128 == 0


def _k_sdpa(q, k, v, mask, scale, causal, dropout_p, key, try_pallas):
    if try_pallas:
        from .attention_pallas import flash_attention_on_mesh

        return flash_attention_on_mesh(q, k, v, causal, scale)
    return _xla_attention(q, k, v, mask, scale, causal, dropout_p, key)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, name=None):
    """query/key/value: [B, H, S, D] (callers reshape). Returns same."""
    d = query.shape[-1]
    sm_scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    dp = dropout_p if training else 0.0
    rng = _random.next_key() if dp > 0.0 else None
    try_pallas = _use_pallas(tuple(query.shape), query.dtype,
                             attn_mask is not None, dp)
    return apply_op("scaled_dot_product_attention", _k_sdpa, query, key,
                    value, attn_mask, scale=sm_scale, causal=bool(is_causal),
                    dropout_p=float(dp), key=rng, try_pallas=try_pallas)
