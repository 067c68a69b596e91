"""GPT-2 — the flagship model (BASELINE config 4: GPT-2 345M Fleet DP).

Reference capability: PaddleNLP GPT trained through fleet hybrid
parallelism (the reference repo itself carries the primitives:
mp_layers.py, pp_layers.py, fused_attention).

TPU-native design decisions:
- The L transformer blocks are ONE set of stacked parameters with a
  leading layer dim, executed with `lax.scan` — XLA compiles one block
  and reuses it L times (fast compiles, and the 'pp' mesh axis shards
  the layer dim: scan + GSPMD resharding = a layer-pipeline over ICI).
- Attention uses the Pallas flash kernel on TPU; other platforms and
  shapes the kernel does not tile take dense XLA attention.
- Every activation carries sharding constraints over (dp, sp, mp) so
  pjit lowers to Megatron-style comm without hand-written collectives.
- The LM head is tied to the (vocab-sharded) embedding; the softmax CE
  over the sharded vocab axis is the ParallelCrossEntropy pattern.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.engine import apply_op, in_trace_mode
from ...core.tensor import Parameter, Tensor
from ...nn.layer.layers import Layer
from ...ops import random as _random
from ...distributed import mesh as mesh_mod
from ...incubate.nn import pallas as _pallas

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt2_small",
           "gpt2_345m"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    ffn_hidden: int = 4096
    max_seq_len: int = 1024
    dropout: float = 0.1
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    use_flash_attention: bool = True
    # sequence-parallel attention over the 'sp' mesh axis. Engages
    # only when the live mesh has sp > 1. sp_attention picks the
    # algorithm: "ring" (KV ppermute ring, O(S/sp) memory) or
    # "ulysses" (head-sharded all_to_all — cheaper when heads >> sp).
    use_ring_attention: bool = False
    sp_attention: str = "ring"
    remat: bool = True  # jax.checkpoint each block (recompute analog)
    # selective remat: None = save nothing (full recompute);
    # "dots" = save matmul/einsum outputs, recompute elementwise only
    # (jax.checkpoint_policies.dots_saveable) — less recompute FLOPs
    # for a modest activation-memory increase
    remat_policy: str | None = None
    # unroll factor for the scan-over-layers (lax.scan unroll=):
    # unrolled, XLA schedules across layer boundaries and drops the
    # per-iteration loop overhead and the dynamic-update-slice traffic
    # that saves residuals. True = fully unroll.
    scan_unroll: int | bool = 1
    # explicit GPipe schedule over the 'pp' mesh axis: num_layers is
    # cut into pp_num_stages stages and the batch into
    # pp_microbatches micro-batches (0 = plain scan-over-layers)
    pp_num_stages: int = 0
    pp_microbatches: int = 0
    # "gpipe": autodiff through the pipelined loop (activation memory
    # grows with micro-batch count M). "1f1b": exact 1F1B — a
    # custom-vjp backward interleaves each micro-batch's forward
    # recompute with backward, so live activations are O(S^2),
    # independent of M (reference forward_backward_pipeline).
    pp_schedule: str = "gpipe"


def _maybe_constrain(x, spec):
    """Sharding constraint when compiling over a mesh (no-op eager)."""
    mesh = mesh_mod.get_mesh()
    if mesh is None:
        return x
    names = tuple(a if (a is None or a in mesh.shape) else None
                  for a in spec)
    if all(n is None for n in names):
        return x
    try:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, P(*names)))
    except (ValueError, TypeError):
        return x


def _attention(q, k, v, n_head, use_flash, use_ring=False):
    b, s, h = q.shape
    d = h // n_head
    q = q.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)
    scale = 1.0 / math.sqrt(d)
    if use_ring:
        # the sp-attention entries own ALL fallback logic (no mesh /
        # sp==1 / indivisible dims -> exact dense attention)
        from ...incubate.nn.ring_attention import (ring_attention,
                                                   ulysses_attention)

        if use_ring not in (True, "ring", "ulysses"):
            raise ValueError(
                f"sp_attention must be 'ring' or 'ulysses', got "
                f"{use_ring!r}")
        attn_fn = (ulysses_attention if use_ring == "ulysses"
                   else ring_attention)
        out = attn_fn(q, k, v, causal=True, sm_scale=scale)
        return out.transpose(0, 2, 1, 3).reshape(b, s, h)
    if (use_flash and _pallas._on_tpu() and s % 128 == 0
            and d in (64, 128)):
        from ...incubate.nn.attention_pallas import (
            flash_attention_on_mesh)

        out = flash_attention_on_mesh(q, k, v, True, scale)
        return out.transpose(0, 2, 1, 3).reshape(b, s, h)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return out.transpose(0, 2, 1, 3).reshape(b, s, h)


def _layer_norm(x, w, b, eps):
    if _pallas.ln_supported(x.shape[-1]):
        return _pallas.fused_layer_norm(x, w, b, eps)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def _residual_layer_norm(add, x, w, b, eps):
    """(LayerNorm(x + add), x + add) — fused into one Pallas pass when
    armed (the fused_bias_dropout_residual_layer_norm epilogue), the
    plain two-op composition otherwise."""
    if _pallas.ln_supported(x.shape[-1]):
        return _pallas.fused_residual_layer_norm(add, x, w, b, eps)
    s = x + add
    return _layer_norm(s, w, b, eps), s


def _dropout(x, rate, key):
    if key is None or rate <= 0.0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def _block(x, bp, key, n_head, eps, use_flash, dropout, use_ring=False):
    """One transformer block; bp holds this layer's parameter slices."""
    k1 = k2 = None
    if key is not None and dropout > 0.0:
        k1, k2 = jax.random.split(key)
    h = _layer_norm(x, bp["ln1_w"], bp["ln1_b"], eps)
    qkv = h @ bp["qkv_w"] + bp["qkv_b"]
    qkv = _maybe_constrain(qkv, ("dp", "sp", "mp"))
    q, k, v = jnp.split(qkv, 3, axis=-1)
    attn = _attention(q, k, v, n_head, use_flash, use_ring)
    attn = attn @ bp["proj_w"] + bp["proj_b"]
    attn = _dropout(attn, dropout, k1)
    h, x = _residual_layer_norm(_maybe_constrain(attn, ("dp", "sp", None)),
                                x, bp["ln2_w"], bp["ln2_b"], eps)
    ffn = h @ bp["fc1_w"] + bp["fc1_b"]
    ffn = jax.nn.gelu(_maybe_constrain(ffn, ("dp", "sp", "mp")))
    ffn = ffn @ bp["fc2_w"] + bp["fc2_b"]
    ffn = _dropout(ffn, dropout, k2)
    x = x + _maybe_constrain(ffn, ("dp", "sp", None))
    return x


def _k_gpt_forward(ids, params, n_head, eps, use_flash, remat,
                   dropout=0.0, key=None, pp_stages=0, pp_microbatches=0,
                   use_ring=False, pp_schedule="gpipe",
                   remat_policy=None, scan_unroll=1):
    x = jnp.take(params["wte"], ids, axis=0)
    pos = jnp.arange(ids.shape[1])
    x = x + jnp.take(params["wpe"], pos, axis=0)
    x = _dropout(x, dropout, key)
    x = _maybe_constrain(x, ("dp", "sp", None))

    blocks = params["blocks"]
    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    layer_keys = (jax.random.split(jax.random.fold_in(key, 1), n_layers)
                  if key is not None and dropout > 0.0 else None)

    def scan_body(carry, xs):
        layer_params, lkey = xs
        if remat:
            if remat_policy not in (None, "dots"):
                raise ValueError(
                    f"remat_policy must be None or 'dots', got "
                    f"{remat_policy!r}")
            pol = (jax.checkpoint_policies.dots_saveable
                   if remat_policy == "dots" else None)
            fn = jax.checkpoint(
                lambda c, lp, lk: _block(c, lp, lk, n_head, eps, use_flash,
                                         dropout, use_ring), policy=pol)
            out = fn(carry, layer_params, lkey)
        else:
            out = _block(carry, layer_params, lkey, n_head, eps, use_flash,
                         dropout, use_ring)
        return out, None

    if pp_stages > 1 and pp_microbatches > 1:
        # explicit GPipe schedule: stages over 'pp', micro-batched loop
        if layer_keys is not None:
            raise ValueError("GPipe path requires dropout=0.0 for now")
        if n_layers % pp_stages:
            raise ValueError(f"{n_layers} layers not divisible into "
                             f"{pp_stages} pipeline stages")
        from ...distributed.pipeline import (gpipe_loop, microbatch,
                                             unmicrobatch)

        lps = n_layers // pp_stages
        stage_blocks = jax.tree_util.tree_map(
            lambda a: a.reshape((pp_stages, lps) + a.shape[1:]), blocks)

        def stage_fn(bp_stack, sx):
            out, _ = jax.lax.scan(lambda c, lp: scan_body(c, (lp, None)),
                                  sx, bp_stack, unroll=scan_unroll)
            return out

        xm = microbatch(x, pp_microbatches)
        ym = gpipe_loop(stage_fn, stage_blocks, xm, pp_stages,
                        schedule=pp_schedule)
        x = unmicrobatch(ym)
    elif layer_keys is not None:
        x, _ = jax.lax.scan(scan_body, x, (blocks, layer_keys),
                            unroll=scan_unroll)
    else:
        x, _ = jax.lax.scan(lambda c, lp: scan_body(c, (lp, None)), x,
                            blocks, unroll=scan_unroll)
    x = _layer_norm(x, params["lnf_w"], params["lnf_b"], eps)
    logits = x @ params["wte"].T  # tied head; vocab-sharded over mp
    logits = _maybe_constrain(logits, ("dp", "sp", "mp"))
    return logits


def _k_gpt_loss(ids, labels, params, n_head, eps, use_flash, remat,
                dropout=0.0, key=None, pp_stages=0, pp_microbatches=0,
                use_ring=False, pp_schedule="gpipe", remat_policy=None,
                scan_unroll=1):
    """Causal-LM loss with the standard next-token shift: position t
    predicts labels[t+1] (HF convention — pass labels=input_ids)."""
    logits = _k_gpt_forward(ids, params, n_head, eps, use_flash, remat,
                            dropout, key, pp_stages, pp_microbatches,
                            use_ring, pp_schedule, remat_policy,
                            scan_unroll)
    # CE as logsumexp - gathered logit: identical math to
    # log_softmax+gather but never materializes the [B,S,V] f32
    # log-probs array — the f32 convert fuses into the two reduction
    # passes and the gather, cutting ~2 GB of HBM traffic per step at
    # the bench config (r5 perf round, profile showed 28.7% of the
    # step in top-level elementwise fusions)
    lg = logits[:, :-1].astype(jnp.float32)
    tgt = labels[:, 1:]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    return jnp.mean(lse - picked)


class GPTModel(Layer):
    """Decoder-only transformer with stacked-layer parameters."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        c = config
        key = _random.next_key()
        ks = jax.random.split(key, 12)
        std = c.initializer_range

        def normal(k, shape):
            return std * jax.random.normal(k, shape, dtype=jnp.float32)

        L, H, F, V, S = (c.num_layers, c.hidden_size, c.ffn_hidden,
                         c.vocab_size, c.max_seq_len)
        self.wte = self._param("wte", normal(ks[0], (V, H)), P("mp", None))
        self.wpe = self._param("wpe", normal(ks[1], (S, H)), None)
        blocks = {
            "ln1_w": (jnp.ones((L, H)), P("pp", None)),
            "ln1_b": (jnp.zeros((L, H)), P("pp", None)),
            "qkv_w": (normal(ks[2], (L, H, 3 * H)), P("pp", None, "mp")),
            "qkv_b": (jnp.zeros((L, 3 * H)), P("pp", "mp")),
            "proj_w": (normal(ks[3], (L, H, H)) / math.sqrt(2 * L),
                       P("pp", "mp", None)),
            "proj_b": (jnp.zeros((L, H)), P("pp", None)),
            "ln2_w": (jnp.ones((L, H)), P("pp", None)),
            "ln2_b": (jnp.zeros((L, H)), P("pp", None)),
            "fc1_w": (normal(ks[4], (L, H, F)), P("pp", None, "mp")),
            "fc1_b": (jnp.zeros((L, F)), P("pp", "mp")),
            "fc2_w": (normal(ks[5], (L, F, H)) / math.sqrt(2 * L),
                      P("pp", "mp", None)),
            "fc2_b": (jnp.zeros((L, H)), P("pp", None)),
        }
        self._block_params = {}
        for name, (val, spec) in blocks.items():
            self._block_params[name] = self._param(
                "blocks." + name, val, spec)
        self.lnf_w = self._param("lnf_w", jnp.ones((H,)), None)
        self.lnf_b = self._param("lnf_b", jnp.zeros((H,)), None)

    def _param(self, name, value, spec):
        p = Parameter(jnp.asarray(value, jnp.float32), name=name)
        p.dist_spec = spec
        # layer-norm scales/shifts stay f32 under amp O2 (reference
        # pure_fp16_initialize skips LayerNorm)
        base = name.rsplit(".", 1)[-1]
        if base.startswith(("ln1_", "ln2_", "lnf_")):
            p.no_amp_cast = True
        self.add_parameter(name.replace(".", "_"), p)
        return p

    def _params_tree(self):
        return {
            "wte": self.wte,
            "wpe": self.wpe,
            "blocks": dict(self._block_params),
            "lnf_w": self.lnf_w,
            "lnf_b": self.lnf_b,
        }

    def forward(self, input_ids):
        c = self.config
        drop = c.dropout if self.training else 0.0
        key = _random.next_key() if drop > 0.0 else None
        return apply_op("gpt_forward", _k_gpt_forward, input_ids,
                        self._params_tree(), n_head=c.num_heads,
                        eps=c.layer_norm_eps,
                        use_flash=c.use_flash_attention, remat=c.remat,
                        dropout=drop, key=key, pp_stages=c.pp_num_stages,
                        pp_microbatches=c.pp_microbatches,
                        use_ring=(c.sp_attention
                                  if c.use_ring_attention else False),
                        pp_schedule=c.pp_schedule,
                        remat_policy=c.remat_policy,
                        scan_unroll=c.scan_unroll)


class GPTForCausalLM(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config

    def forward(self, input_ids, labels=None):
        if labels is None:
            return self.gpt(input_ids)
        c = self.config
        drop = c.dropout if self.training else 0.0
        key = _random.next_key() if drop > 0.0 else None
        return apply_op("gpt_loss", _k_gpt_loss, input_ids, labels,
                        self.gpt._params_tree(), n_head=c.num_heads,
                        eps=c.layer_norm_eps,
                        use_flash=c.use_flash_attention, remat=c.remat,
                        dropout=drop, key=key, pp_stages=c.pp_num_stages,
                        pp_microbatches=c.pp_microbatches,
                        use_ring=(c.sp_attention
                                  if c.use_ring_attention else False),
                        pp_schedule=c.pp_schedule,
                        remat_policy=c.remat_policy,
                        scan_unroll=c.scan_unroll)


def gpt2_small(**kw):
    return GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                     num_heads=12, ffn_hidden=3072, **kw)


def gpt2_345m(**kw):
    """GPT-2 medium / Megatron 345M (BASELINE config 4)."""
    return GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                     num_heads=16, ffn_hidden=4096, **kw)
