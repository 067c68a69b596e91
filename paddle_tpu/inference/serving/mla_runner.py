"""The second runner: latent-attention (MLA) models with routed
experts (`text/models/glm4_moe_lite.py`), served from ONE pool.

What a token leaves in the cache is `[normalised latent | rotated
key]`, `kv_lora_rank + qk_rope_head_dim` values a layer (576 for
GLM-4.7-Flash, stored in rows of 640: `MLARunner`) — no per-head
keys, no V pool. `prefill_step` attends
densely over the prompt (keys and values expanded through W_kvb, as
in training) and scatters every position's row through the block
table; `decode_step` writes the new token's row and attends in the
ABSORBED form, reading nothing but the rows (`mla_attend_absorbed`).
Both compute the block with the model's own functions, so the
serving path is the training mathematics.

The pool follows `model_runner._scan_layers_paged`'s rule: `[L, N,
BS, row]` in the layer scans' carry, donated, scattered into at
`(l, blk, off)`, read as `[L*N, BS, row]` with the block tables
shifted by `l * N`; never sliced by layer or stacked. The dense
leading layers and the expert layers have different trees, so they
are two scans over one pool; the routed experts' stacked weights are
not among a scan's `xs` (slicing a layer out would copy all its
experts): the grouped matmul reads them as `[L*E, ...]` groups
(`moe.dropless`).

Beside the tokens both programs return `moe_counts` `[expert layers,
experts]`, the live tokens each expert took: the engine's
`serve/moe/*` counters. No verify or tail program yet: the engine
refuses `spec_k > 1` and `prefix_cache` for this runner.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...text.models import glm4_moe_lite as _glm
from .kv_cache import NULL_BLOCK
from .model_runner import _scatter_positions, sample_tokens

__all__ = ["MLARunner", "prefill_step", "decode_step"]

_EXPERTS = ("w13", "w2")     # read in place, never a scan's xs


def _layer_trees(params):
    """(dense stack, expert stack less the routed experts, the routed
    experts' stacks)."""
    moe = params["moe"]
    return (params["dense"],
            {k: v for k, v in moe.items() if k not in _EXPERTS},
            {k: moe[k] for k in _EXPERTS})


def _widen(rows, width):
    """Latent rows zero-padded to the pool's row width."""
    pad = [(0, 0)] * (rows.ndim - 1) + [(0, width - rows.shape[-1])]
    return jnp.pad(rows, pad)


def _finish(params, x, cfg):
    """Final norm and the untied head, logits in float32."""
    x = _glm.rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
    return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


def _run_layers(params, x, carry, attend, live, cfg):
    """Both stacks over `x [T, hidden]`. `attend(x, carry, ap, layer)
    -> (attention output [T, heads * v], carry, ys)` is the program's
    own (dense over the prompt, or absorbed through the pool, which
    is then the carry); `layer` counts from the first dense layer.
    Returns (x, carry, dense ys, expert-layer ys, moe_counts)."""
    eps = cfg.rms_norm_eps
    dense, moe, experts = _layer_trees(params)
    n_dense = jax.tree_util.tree_leaves(dense)[0].shape[0]
    n_moe = jax.tree_util.tree_leaves(moe)[0].shape[0]

    def block(x, carry, ap, layer):
        with jax.named_scope("mla/attend"):
            attn, carry, ys = attend(
                _glm.rms_norm(x, ap["ln1"], eps), carry, ap, layer)
        h = x + attn @ ap["wo"]
        return h, _glm.rms_norm(h, ap["ln2"], eps), carry, ys

    def dense_layer(c, xs):
        lp, layer = xs
        h, u, carry, ys = block(*c, lp["attn"], layer)
        return (h + _glm.swiglu(u, lp["w13"], lp["w2"]), carry), ys

    def moe_layer(c, xs):
        lp, layer = xs
        h, u, carry, ys = block(*c, lp["attn"], n_dense + layer)
        out, counts = _glm.moe_ffn(u, {**lp, **experts}, cfg,
                                   layer=layer, live=live)
        return (h + out, carry), (ys, counts)

    (x, carry), ys_d = jax.lax.scan(
        dense_layer, (x, carry),
        (dense, jnp.arange(n_dense, dtype=jnp.int32)))
    (x, carry), (ys_m, counts) = jax.lax.scan(
        moe_layer, (x, carry),
        (moe, jnp.arange(n_moe, dtype=jnp.int32)))
    return x, carry, ys_d, ys_m, counts


def prefill_step(params, ids, prompt_len, pools, block_table,
                 temperature, top_k, seed, *, cfg, block_size):
    """Causal forward over one block-padded prompt, ids [1, P].
    Writes all P positions' latent rows through `block_table` (the
    padded tail lands where decode overwrites it before any masked
    read, or in the NULL block) and samples the first token from the
    last real row. Returns (token [], (pool,), {"moe_counts"}), the
    counts over the `prompt_len` real tokens."""
    (pool,) = pools
    p_len = ids.shape[1]
    positions = jnp.arange(p_len)

    def attend(u, carry, ap, layer):
        q_nope, q_rope = _glm.mla_query(u, ap, cfg, positions)
        latent = _glm.mla_latent(u, ap, cfg, positions)
        return (_glm.mla_attend_dense(q_nope, q_rope, latent, ap, cfg),
                carry, latent)

    x = jnp.take(params["embed"], ids[0], axis=0)
    x, _, rows_d, rows_m, counts = _run_layers(
        params, x, (), attend, positions < prompt_len, cfg)
    # one batched scatter, the layer an index like blk and off
    # (model_runner.prefill_step)
    rows = _widen(jnp.concatenate([rows_d, rows_m], 0),  # [L, P, row]
                  pool.shape[-1])
    blk, off = _scatter_positions(block_table, positions, block_size)
    layers = jnp.arange(pool.shape[0])[:, None]
    pool = pool.at[layers, blk, off].set(rows.astype(pool.dtype))

    last = jax.lax.dynamic_index_in_dim(x, prompt_len - 1, axis=0,
                                        keepdims=False)
    logits = _finish(params, last, cfg)                  # [V]
    token = sample_tokens(logits[None], temperature[None], top_k[None],
                          seed[None])[0]
    return token, (pool,), {"moe_counts": counts}


def decode_step(params, ids, positions, pools, block_tables,
                context_lens, temperature, top_k, seeds, *, cfg,
                block_size, use_kernel=False, interpret=False):
    """One generation step for the whole running batch, ids and
    positions [B]; `context_lens[b] == positions[b] + 1`. Each layer
    writes this token's latent row at (tables[b, pos // BS], pos %
    BS) BEFORE attending, then attends in the absorbed form over the
    rows its table names. Inactive slots (table all NULL) ride along
    and are left out of the routing counts. Returns (tokens [B],
    (pool,), {"moe_counts"})."""
    if use_kernel:
        raise NotImplementedError(
            "no paged latent-attention kernel yet: use_kernel=False")
    (pool,) = pools
    n_layers, n_blocks = pool.shape[:2]
    flat = (n_layers * n_blocks,) + pool.shape[2:]
    bsz = ids.shape[0]
    blk = jnp.take_along_axis(
        block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
    off = positions % block_size

    def attend(u, pool, ap, layer):
        q_nope, q_rope = _glm.mla_query(u, ap, cfg, positions)
        row = _widen(_glm.mla_latent(u, ap, cfg, positions),
                     pool.shape[-1])
        pool = pool.at[layer, blk, off].set(row.astype(pool.dtype))
        # whole blocks as they lie, in table order (indexing clamps:
        # no out-of-bounds fill pass over the gathered rows)
        ctx = pool.reshape(flat)[block_tables + layer * n_blocks]
        ctx = ctx.reshape(bsz, -1, ctx.shape[-1])        # [B, T, row]
        return (_glm.mla_attend_absorbed(q_nope, q_rope, ctx,
                                         context_lens, ap, cfg),
                pool, None)

    x = jnp.take(params["embed"], ids, axis=0)
    x, pool, _, _, counts = _run_layers(
        params, x, pool, attend, block_tables[:, 0] != NULL_BLOCK, cfg)
    tokens = sample_tokens(_finish(params, x, cfg), temperature, top_k,
                           seeds)
    return tokens, (pool,), {"moe_counts": counts}


class MLARunner:
    """How LLMEngine serves a Glm4MoeLiteForCausalLM: one pool of
    `latent_row` values a token a layer; prefill and decode, no
    verify, tail or draft."""

    verify_step = prefill_tail_step = draft_params = None

    def __init__(self, model):
        model = getattr(model, "model", model)
        self.config = cfg = model.config
        self.params = jax.tree_util.tree_map(
            lambda p: p._value, model._params_tree())
        # the row as stored: padded with zeros to whole 128-lane
        # tiles (576 -> 640 values). With 576 as the minor dimension
        # the TPU stores the pool with the BLOCK axis on the lanes
        # and every program relays it both ways (two 2 GiB copies in
        # the decode program compiled for the v5e, none at 640)
        self.pool_rows = (-(-cfg.latent_row // 128) * 128,)
        self.prefill_step = functools.partial(prefill_step, cfg=cfg)
        self.decode_step = functools.partial(decode_step, cfg=cfg)

    def kernel_supported(self, block_size):
        return False
