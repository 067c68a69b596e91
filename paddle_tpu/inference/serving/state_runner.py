"""The third runner: models that keep TWO kinds of state a sequence
— keys and values in paged pools, addressed through block tables,
and beside them a state of fixed size (the tail of a short
convolution's window: `text/models/lfm2_moe.py`), addressed by the
sequence's SLOT in the decode batch.

What a layer is the runner reads from the model and names no model:
`model.state_layers(params, x, carry, attend, window, positions,
live, cfg)` runs the model's own layers and calls the program's

    attend(q [T, Hq, D], k [T, Hkv*D], v [T, Hkv*D], carry, a)
    window(z [T, H], carry, c) -> (z_{t-n} .. z_t [T, n + 1, H], carry)

once for every attention `a` and every windowed layer `c`;
`model.n_attentions` is the K/V pools' layer count,
`model.kv_heads` the heads (query heads may be a multiple of the
K/V heads: the pools hold the K/V heads' rows, `Hkv * D` a token),
`model.slot_state` the per-slot arrays (`(layers, n, width)`: the
last `n` rows of every windowed layer's stream). So the serving
path is the training mathematics.

The carry is the engine's `pools`: the K and V pools `[A, N, BS,
Hkv*D]` and after them the state `[C, max_batch, n, H]`
(`kv_cache.PagedKVCache`), donated and updated in place: rows
scattered at `(a, blk, off)` and read as `[A*N, ...]` with the
tables shifted by `a * N` (`model_runner._scan_layers_paged`'s
rule; the layers are unrolled, `a` and `c` are Python numbers).

Attentions of several kinds (PR 38): a model whose attentions differ
in what they keep (some the whole context, some a sliding window)
says so as `model.attention_cache[a] = (group, index in the group,
window)` and the runner, still naming no model, hands the engine one
window a CACHE GROUP (`cache_groups`; `kv_cache.PagedKVCache`): the
pools are then one group's layers deep, `[g, N, BS, Hkv*D]`, the
tables come one a group (`[groups, ...]`, by logical block, NULL
where a window group's block was freed or never granted), attention
`a` scatters and reads through ITS group's table at pool layer
`index`, its window goes to the dense prefill attention
(`model.attend_dense(.., window=)`: a prompt's rows from before the
window land in the NULL block) and to the paged kernel, which then
walks the window's page groups alone. A model without
`attention_cache` (one group, no window) traces to the programs it
had.

- `prefill_step` attends densely over the prompt
  (`model.attend_dense`), scatters every position's K/V rows
  through the block table, and writes the window's tail AT THE
  PROMPT'S END (`z[prompt_len - n : prompt_len]`, zeros before
  position 0; not at the padded bucket's end) into the request's
  slot: `slot` is one more argument, after `seed`.
- `decode_step`: the batch row IS the slot. A windowed layer reads
  its `[B, n, H]` state, appends this token's row, drops the oldest
  and writes the state back whole, in place, with no gather;
  inactive slots ride along and whatever they leave is overwritten
  whole by the next prefill into the slot. An attention writes the
  token's K and V rows and attends through the tables: in the
  Pallas paged kernel with grouped heads where `kernel_supported`
  says so (a TPU), else over a dense gather (the CPU's path, and
  the reference the kernel is tested against).

No verify or tail program: a rejected draft token or a shared
prefix would need the state as it was at another position
(snapshots at block boundaries: ROADMAP R5). The engine refuses
`spec_k > 1` and `prefix_cache` for this runner.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...incubate.distributed.models.moe.dropless import (
    expert_kernel_supported)
from .kv_cache import NULL_BLOCK
from .model_runner import _scatter_positions, sample_tokens

__all__ = ["StateRunner", "prefill_step", "decode_step"]


def _window_tail(zp, prompt_len, n):
    """The `n` rows a sequence keeps of a windowed layer after its
    prefill: the last before `prompt_len`, the prompt's REAL end (a
    prompt is padded to its bucket, and the bucket's end holds the
    padding's rows). `zp` is the stream with `n` zero rows in front,
    so that a prompt shorter than `n` keeps zeros."""
    return jax.lax.dynamic_slice_in_dim(zp, prompt_len, n)


def _layout(layout, a):
    """(cache group, pool layer, window) of attention `a`: the
    model's `attention_cache`, or one group of every attention."""
    return (0, a, None) if layout is None else layout[a]


def prefill_step(params, ids, prompt_len, pools, block_table,
                 temperature, top_k, seed, slot=None, *, cfg, model,
                 block_size, layout=None):
    """Causal forward over one block-padded prompt, ids [1, P], for
    the request that will decode in batch row `slot`. Writes all P
    positions' K/V rows through `block_table` (the padded tail lands
    where decode overwrites it before any masked read, or in the
    NULL block), every windowed layer's last rows before
    `prompt_len` into `state[:, slot]`, and samples the first token
    from the last real row. Returns (token [], pools, the model's
    routing counts over the `prompt_len` real tokens)."""
    p_len = ids.shape[1]
    positions = jnp.arange(p_len)
    # a table a cache group where the model has several
    tables = (block_table,) if layout is None else tuple(block_table)
    blks = [_scatter_positions(t, positions, block_size) for t in tables]

    def attend(q, k, v, carry, a):
        kp, vp, *state = carry
        group, layer, win = _layout(layout, a)
        blk, off = blks[group]
        kp = kp.at[layer, blk, off].set(k.astype(kp.dtype))
        vp = vp.at[layer, blk, off].set(v.astype(vp.dtype))
        out = model.attend_dense(q, k, v) if win is None \
            else model.attend_dense(q, k, v, window=win)
        return out, (kp, vp, *state)

    def window(z, carry, c):
        *kv, st = carry
        n = st.shape[2]
        zp = jnp.pad(z, ((n, 0), (0, 0)))      # zp[t + n] = z[t]
        win = jnp.stack([zp[j:j + p_len] for j in range(n + 1)], 1)
        tail = _window_tail(zp, prompt_len, n)
        st = st.at[c, slot].set(tail.astype(st.dtype))
        return win, (*kv, st)

    x = jnp.take(params["embed"], ids[0], axis=0)
    x, pools, stats = model.state_layers(
        params, x, tuple(pools), attend, window, positions,
        positions < prompt_len, cfg)
    last = jax.lax.dynamic_index_in_dim(x, prompt_len - 1, axis=0,
                                        keepdims=False)
    token = sample_tokens(model.logits(params, last, cfg)[None],
                          temperature[None], top_k[None], seed[None])[0]
    return token, pools, stats


def decode_step(params, ids, positions, pools, block_tables,
                context_lens, temperature, top_k, seeds, *, cfg, model,
                block_size, use_kernel=False, interpret=False,
                layout=None):
    """One generation step for the whole running batch, ids and
    positions [B]; `context_lens[b] == positions[b] + 1`. Row b is
    slot b: its windows' tails are `state[:, b]`. Each attention
    writes this token's K/V rows at (tables[b, pos // BS], pos % BS)
    BEFORE attending. Inactive slots (table all NULL) ride along and
    are left out of the routing counts. Returns (tokens [B], pools,
    the model's routing counts)."""
    from ...incubate.nn.pallas import paged_attention as _pa

    n_attn, n_blocks = pools[0].shape[:2]
    bsz = ids.shape[0]
    # a table a cache group where the model has several: `[groups,
    # B, MAXB]`, indexed by the logical block
    group_tables = (block_tables,) if layout is None \
        else tuple(block_tables)
    blks = [jnp.take_along_axis(
        t, (positions // block_size)[:, None], axis=1)[:, 0]
        for t in group_tables]
    off = positions % block_size

    def attend(q, k, v, carry, a):
        kp, vp, *state = carry
        group, layer, win = _layout(layout, a)
        blk = blks[group]
        kp = kp.at[layer, blk, off].set(k.astype(kp.dtype))
        vp = vp.at[layer, blk, off].set(v.astype(vp.dtype))
        # the whole pools as one run of blocks, this attention's at
        # `layer * n_blocks`: never sliced, never stacked
        d = q.shape[-1]
        flat = (n_attn * n_blocks, block_size, kp.shape[-1] // d, d)
        tables = group_tables[group] + layer * n_blocks
        scale = 1.0 / math.sqrt(d)
        if use_kernel:
            out = _pa.paged_attention(
                q, kp.reshape(flat), vp.reshape(flat), tables,
                context_lens, sm_scale=scale, interpret=interpret,
                window=win)
        else:
            out = _pa.paged_attention_reference(
                q, kp.reshape(flat), vp.reshape(flat), tables,
                context_lens, sm_scale=scale, window=win)
        return out.reshape(bsz, -1), (kp, vp, *state)

    def window(z, carry, c):
        *kv, st = carry
        win = jnp.concatenate([st[c], z[:, None].astype(st.dtype)], 1)
        return win, (*kv, st.at[c].set(win[:, 1:]))

    x = jnp.take(params["embed"], ids, axis=0)
    x, pools, stats = model.state_layers(
        params, x, tuple(pools), attend, window, positions,
        group_tables[0][:, 0] != NULL_BLOCK, cfg)
    tokens = sample_tokens(model.logits(params, x, cfg), temperature,
                           top_k, seeds)
    return tokens, pools, stats


class StateRunner:
    """How LLMEngine serves a model with `state_layers`: a K and a V
    pool of `Hkv * D` values a token an attention, per-slot state
    beside them, prefill (told its slot) and decode (through the
    paged kernel where `kernel_supported`); no verify, tail or
    draft."""

    verify_step = prefill_tail_step = draft_params = None

    def __init__(self, model):
        model = getattr(model, "model", model)
        self.config = cfg = model.config
        self.params = jax.tree_util.tree_map(
            lambda p: p._value, model._params_tree())
        self.heads = hq, hkv, d = model.kv_heads
        self.pool_rows = (hkv * d,) * 2
        self.pool_layers = model.n_attentions
        self.cache_groups = (None,)
        self.routed_experts = model.routed_experts
        self.slot_state = tuple(model.slot_state)
        # the programs read the model's functions, not the instance
        kw = dict(cfg=cfg, model=type(model))
        layout = getattr(model, "attention_cache", None)
        if layout is not None:
            # attentions of several kinds: a window a cache group,
            # the pools one group's layers deep
            kw["layout"] = layout = tuple(layout)
            windows = {}
            for group, _, win in layout:
                if windows.setdefault(group, win) != win:
                    raise ValueError(
                        f"cache group {group} holds attentions of two "
                        f"windows: {layout}")
            self.cache_groups = tuple(
                windows[g] for g in range(len(windows)))
            if self.cache_groups[0] is not None:
                # its first block says whether a slot is live
                raise ValueError("cache group 0 keeps the whole context")
            self.pool_layers = 1 + max(layer for _, layer, _ in layout)
        self.prefill_step = functools.partial(prefill_step, **kw)
        self.decode_step = functools.partial(decode_step, **kw)

    def kernel_supported(self, block_size):
        """Does the Pallas paged-attention kernel take this model's
        grouped heads at this block size, here?"""
        from ...incubate.nn.pallas import paged_attention as _pa

        hq, hkv, d = self.heads
        return _pa.paged_decode_supported(hq, d, block_size,
                                          num_kv_heads=hkv)

    def experts_kernel(self, tokens):
        """Does a program over `tokens` rows multiply its experts'
        groups in the Pallas grouped matmul here? What that program
        asked while it was traced (`dropless.expert_kernel_
        supported`), for the model's `routed_experts`."""
        return expert_kernel_supported(tokens, *self.routed_experts)
