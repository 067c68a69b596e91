"""The second runner: latent-attention (MLA) models
(`text/models/mla.py`: `glm4_moe_lite`, `longcat_flash`), served from
ONE pool.

What a token leaves in the cache is `[normalised latent | rotated
key]`, `kv_lora_rank + qk_rope_head_dim` values an ATTENTION (576 at
the published widths, stored in rows of 640: `MLARunner`) — no
per-head keys, no V pool. `prefill_step` attends densely over the
prompt (keys and values expanded through W_kvb, as in training) and
scatters every position's row through the block table; `decode_step`
writes the new token's row and attends in the ABSORBED form, reading
nothing but the rows: THROUGH THE BLOCK TABLES in one Pallas launch
an attention where `MLARunner.kernel_supported` says so (a TPU; each
live page copied once, key and value to every head:
`mla_attend_paged`, `pallas.paged_attention.paged_latent_attention`),
else over a dense gather of every sequence's whole table
(`mla_attend_absorbed`: the CPU's path, and the reference the kernel
is tested against).

What a layer IS the runner reads from the model and names no model:
`model.mla_layers(params, x, carry, attend, live, cfg)` runs the
model's own stacks (GLM: a dense then an expert FFN behind one
attention; LongCat-Flash: two attentions, two dense FFNs and experts
whose output crosses a sub-layer) and calls the program's `attend`
once for every attention with that attention's number in the cache;
`model.n_attentions` is how many there are, which is the pool's
layer count and need not be the model's. So the serving path is the
training mathematics, and there is one prefill and one decode.

The pool follows `model_runner._scan_layers_paged`'s rule: `[A, N,
BS, row]` in the layer scans' carry, donated, scattered into at
`(a, blk, off)`, read as `[A*N, BS, row]` with the block tables
shifted by `a * N`; never sliced by attention or stacked (the kernel
reads it so too: the pool stays where it is).

Beside the tokens both programs return the model's routing counts
(`moe_counts` `[expert layers, experts held]`, the live tokens each
expert took, and for a router wider than the experts held
`moe_picks`): the engine's `serve/moe/*` counters. No verify or tail
program yet: the engine refuses `spec_k > 1` and `prefix_cache` for
this runner.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...incubate.distributed.models.moe.dropless import (
    expert_kernel_supported)
from ...text.models import mla as _mla
from .kv_cache import NULL_BLOCK
from .model_runner import _scatter_positions, sample_tokens

__all__ = ["MLARunner", "prefill_step", "decode_step"]


def _widen(rows, width):
    """Latent rows zero-padded to the pool's row width."""
    pad = [(0, 0)] * (rows.ndim - 1) + [(0, width - rows.shape[-1])]
    return jnp.pad(rows, pad)


def _finish(params, x, cfg):
    """Final norm and the untied head, logits in float32."""
    x = _mla.rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
    return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


def prefill_step(params, ids, prompt_len, pools, block_table,
                 temperature, top_k, seed, *, cfg, layers, block_size):
    """Causal forward over one block-padded prompt, ids [1, P].
    Writes all P positions' latent rows through `block_table` (the
    padded tail lands where decode overwrites it before any masked
    read, or in the NULL block) and samples the first token from the
    last real row. Returns (token [], (pool,), the model's routing
    counts over the `prompt_len` real tokens)."""
    (pool,) = pools
    p_len = ids.shape[1]
    positions = jnp.arange(p_len)

    def attend(u, carry, ap, layer):
        q_nope, q_rope = _mla.mla_query(u, ap, cfg, positions)
        latent = _mla.mla_latent(u, ap, cfg, positions)
        return (_mla.mla_attend_dense(q_nope, q_rope, latent, ap, cfg),
                carry, latent)

    x = jnp.take(params["embed"], ids[0], axis=0)
    x, _, rows, stats = layers(
        params, x, (), attend, positions < prompt_len, cfg)
    # one batched scatter, the attention an index like blk and off
    # (model_runner.prefill_step)
    rows = _widen(rows, pool.shape[-1])                  # [A, P, row]
    blk, off = _scatter_positions(block_table, positions, block_size)
    attns = jnp.arange(pool.shape[0])[:, None]
    pool = pool.at[attns, blk, off].set(rows.astype(pool.dtype))

    last = jax.lax.dynamic_index_in_dim(x, prompt_len - 1, axis=0,
                                        keepdims=False)
    logits = _finish(params, last, cfg)                  # [V]
    token = sample_tokens(logits[None], temperature[None], top_k[None],
                          seed[None])[0]
    return token, (pool,), stats


def decode_step(params, ids, positions, pools, block_tables,
                context_lens, temperature, top_k, seeds, *, cfg, layers,
                block_size, use_kernel=False, interpret=False):
    """One generation step for the whole running batch, ids and
    positions [B]; `context_lens[b] == positions[b] + 1`. Each
    attention writes this token's latent row at (tables[b, pos //
    BS], pos % BS) BEFORE attending, then attends in the absorbed
    form over the rows its table names: with `use_kernel` through
    the tables in the Pallas latent kernel (`interpret`: under the
    interpreter, the CPU's parity tests), else over a dense gather.
    Inactive slots (table all NULL) ride along and are left out of
    the routing counts. Returns (tokens [B], (pool,), the model's
    routing counts)."""
    (pool,) = pools
    n_layers, n_blocks = pool.shape[:2]
    flat = (n_layers * n_blocks,) + pool.shape[2:]
    bsz = ids.shape[0]
    blk = jnp.take_along_axis(
        block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
    off = positions % block_size

    def attend(u, pool, ap, layer):
        q_nope, q_rope = _mla.mla_query(u, ap, cfg, positions)
        row = _widen(_mla.mla_latent(u, ap, cfg, positions),
                     pool.shape[-1])
        pool = pool.at[layer, blk, off].set(row.astype(pool.dtype))
        # the whole pool as one run of blocks, this attention's at
        # `layer * n_blocks`: never sliced, never stacked
        rows = pool.reshape(flat)
        tables = block_tables + layer * n_blocks
        if use_kernel:
            return (_mla.mla_attend_paged(
                q_nope, q_rope, rows, tables, context_lens, ap, cfg,
                interpret=interpret), pool, None)
        # whole blocks as they lie, in table order (indexing clamps:
        # no out-of-bounds fill pass over the gathered rows)
        ctx = rows[tables]
        ctx = ctx.reshape(bsz, -1, ctx.shape[-1])        # [B, T, row]
        return (_mla.mla_attend_absorbed(q_nope, q_rope, ctx,
                                         context_lens, ap, cfg),
                pool, None)

    x = jnp.take(params["embed"], ids, axis=0)
    x, pool, _, stats = layers(
        params, x, pool, attend, block_tables[:, 0] != NULL_BLOCK, cfg)
    tokens = sample_tokens(_finish(params, x, cfg), temperature, top_k,
                           seeds)
    return tokens, (pool,), stats


class MLARunner:
    """How LLMEngine serves a model with `mla_layers`: one pool of
    `latent_row` values a token an attention; prefill and decode
    (through the paged latent kernel where `kernel_supported`), no
    verify, tail or draft."""

    verify_step = prefill_tail_step = draft_params = None
    slot_state = ()

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
        self.pool_layers = model.n_attentions
        self.routed_experts = model.routed_experts
        kw = dict(cfg=cfg, layers=model.mla_layers)
        self.prefill_step = functools.partial(prefill_step, **kw)
        self.decode_step = functools.partial(decode_step, **kw)

    def kernel_supported(self, block_size):
        """Does decode attend through the Pallas latent kernel here?
        `paged_decode_supported`'s answer (a TPU, or the interpreter
        on the CPU; no live multi-device mesh; whole 128-lane rows,
        whole sublane groups of a block) for ONE shared head as wide
        as the stored row."""
        from ...incubate.nn.pallas import paged_attention as _pa

        return _pa.paged_decode_supported(1, self.pool_rows[0],
                                          block_size)

    def experts_kernel(self, tokens):
        """Does a program over `tokens` rows multiply its experts'
        groups in the Pallas grouped matmul here? What that program
        asked while it was traced (`dropless.expert_kernel_
        supported`), for the model's `routed_experts`."""
        return expert_kernel_supported(tokens, *self.routed_experts)
