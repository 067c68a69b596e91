"""The runner of every model that hands the serving path its layers
(GLM-4.7-Flash, LongCat-Flash, LFM2, Mellum2, Falcon-H1; GPT-2 has
`model_runner.GPT2Runner`). It reads the model's declarations and
names no model:

    embed(params, ids, cfg), logits(params, x, cfg)
    decoder_layers(params, x, carry, attend, window, scan, positions,
                   live, cfg) -> (x, carry, rows or None, stats)
    n_attentions, routed_experts (or None)
    kv_heads (Hq, Hkv, D) and attend_dense, OR latent_row
    optionally slot_state, attention_cache, ssm_heads

What a token leaves in the cache is the one choice, made at
construction: K and V pools of `Hkv * D` values a token an attention
(`kv_heads`; query heads a multiple of the K/V heads), read over a
prompt by the model's `attend_dense`; or ONE pool of latent rows
`[c_kv | k_rope]` (`latent_row`: 576 values at the published widths,
stored zero-padded in 640), read as key and value, in `mla.py`'s
dense form over a prompt and its absorbed form in decode. The layers
call the program's

    attend(q [T, Hq, D], k [T, Hkv*D], v [T, Hkv*D], carry, a)
        -> (out [T, Hq*D], carry)                            K/V rows
    attend(u [T, H], carry, ap, a) -> (out, carry, row or None)
                                                          latent rows
    window(z [T, H], carry, c) -> (z_{t-n} .. z_t [T, n + 1, H], carry)
    scan(x [T, H, P], dt [T, H], A [H], B [T, G, N], C [T, G, N],
         carry, s) -> (y [T, H, P] float32, carry)

once for every attention `a`, windowed layer `c` and state-space
layer `s` (`incubate/nn/ssm.py`; `y` without the skip term). So the
serving path is the training mathematics.

The carry is the engine's `pools`: the K and V pools `[A, N, BS,
Hkv*D]` (or the latent pool `[A, N, BS, row]`) and after them the
per-slot arrays `[layers, max_batch, *shape]` of `model.slot_state`
(`(kind, (layers, *shape a layer), dtype)` each, dtype None: the
pools'; kind `window` the last `n` rows of every windowed layer's
stream, kind `ssm` every state-space layer's state `(H, N, P)`),
donated and updated in place: rows scattered at `(a, blk, off)` and
read as `[A*N, ...]` with the tables shifted by `a * N`
(`model_runner._scan_layers_paged`'s rule: never sliced or stacked,
and the kernels read it so too).

Attentions of several kinds say so as
`model.attention_cache[a] = (group, index in the group, window)`: the
runner hands the engine one window a CACHE GROUP (`cache_groups`;
`kv_cache.PagedKVCache`), the pools are one group's layers deep, the
tables come one a group (`[groups, ...]`, NULL where a window group's
block was freed), attention `a` scatters and reads through ITS
group's table at pool layer `index`, and its window goes to
`attend_dense(.., window=)` and to the paged kernel, which walks the
window's page groups alone. Without `attention_cache` (one group, no
window) a model traces to the programs it had.

- `prefill_step` attends densely over the prompt and writes every
  position's rows through the block table (K/V rows by each attention;
  latent rows, which the layers return, once after them). Each
  per-slot state is written as the prompt's REAL end leaves it (not
  the padded bucket's) into the request's `slot`, one more argument
  after `seed`: a window's tail `z[prompt_len - n : prompt_len]`, a
  state-space layer's state after `prompt_len - 1` (`ssm.ssd_chunked`
  from a zero state, scope `ssd`, the padded tail given dt = 0).
- `decode_step`: batch row b IS slot b. A windowed layer appends the
  token's row to its `[B, n, H]` state and writes it back whole; a
  state-space layer updates its `[B, H, N, P]` state in place (scope
  `state`: `pallas/ssm_state.py` where `ssm_state_supported`, a TPU,
  else `ssm.ssm_step`). An attention writes the token's rows BEFORE
  attending through the tables: in a Pallas paged kernel where
  `kernel_supported` (a TPU: grouped heads, or latent rows copied once
  a page as key and value), else over a dense gather (the CPU's path,
  and the kernels' reference). Inactive slots ride along.

Both return the model's routing counts beside the tokens
(`moe_counts`, and `moe_picks` for a router wider than the experts
held): the engine's `serve/moe/*` counters. No verify or tail
program: a rejected draft token or a shared prefix would need the
state as it was at another position (ROADMAP R5); the engine refuses
`spec_k > 1` and `prefix_cache` for this runner.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...incubate.distributed.models.moe.dropless import (
    expert_kernel_supported)
from ...text.models import mla as _mla
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


def _state_at(states, kind):
    """Where the per-slot array of `kind` is in the carry: behind the
    K and V pools, in the order the model declares them."""
    return 2 + states.index(kind)


def _put(carry, i, value):
    return carry[:i] + (value,) + carry[i + 1:]


def _widen(rows, width):
    """Latent rows zero-padded to the pool's row width."""
    pad = [(0, 0)] * (rows.ndim - 1) + [(0, width - rows.shape[-1])]
    return jnp.pad(rows, pad)


# -- what a token leaves in the cache: the two kinds ------------------------

def _kv_prefill(model, cfg, pools, tables, positions, layout, block_size):
    """K and V pools: each attention scatters its rows through its
    group's table and attends over the prompt in `model.attend_dense`
    (with its window). Returns (attend, the layers' carry: the pools,
    what writes the rows the layers return: nothing)."""
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

    return attend, tuple(pools), lambda carry, rows: carry


def _latent_prefill(model, cfg, pools, tables, positions, layout,
                    block_size):
    """One latent pool: each attention attends densely over the prompt
    (keys and values expanded through W_kvb, as in training) and gives
    the layers its rows; `write` scatters all of them once (the padded
    tail lands where decode overwrites it before any masked read, or
    in the NULL block). Returns (attend, the layers' carry: nothing,
    write)."""
    def attend(u, carry, ap, a):
        q_nope, q_rope = _mla.mla_query(u, ap, cfg, positions)
        latent = _mla.mla_latent(u, ap, cfg, positions)
        return (_mla.mla_attend_dense(q_nope, q_rope, latent, ap, cfg),
                carry, latent)

    def write(carry, rows):
        (pool,) = pools
        rows = _widen(rows, pool.shape[-1])              # [A, P, row]
        blk, off = _scatter_positions(tables[0], positions, block_size)
        attns = jnp.arange(pool.shape[0])[:, None]
        return (pool.at[attns, blk, off].set(rows.astype(pool.dtype)),)

    return attend, (), write


def _kv_decode(cfg, tables, blks, off, positions, context_lens, layout,
               block_size, use_kernel, interpret):
    """K and V pools: write the token's rows, attend through the
    tables with grouped heads (the window, if any, walked alone)."""
    from ...incubate.nn.pallas import paged_attention as _pa

    def attend(q, k, v, carry, a):
        kp, vp, *state = carry
        n_attn, n_blocks = kp.shape[:2]
        group, layer, win = _layout(layout, a)
        blk = blks[group]
        kp = kp.at[layer, blk, off].set(k.astype(kp.dtype))
        vp = vp.at[layer, blk, off].set(v.astype(vp.dtype))
        # the whole pools as one run of blocks, this attention's at
        # `layer * n_blocks`: never sliced, never stacked
        d = q.shape[-1]
        flat = (n_attn * n_blocks, block_size, kp.shape[-1] // d, d)
        table = tables[group] + layer * n_blocks
        scale = 1.0 / math.sqrt(d)
        if use_kernel:
            out = _pa.paged_attention(
                q, kp.reshape(flat), vp.reshape(flat), table,
                context_lens, sm_scale=scale, interpret=interpret,
                window=win)
        else:
            out = _pa.paged_attention_reference(
                q, kp.reshape(flat), vp.reshape(flat), table,
                context_lens, sm_scale=scale, window=win)
        return out.reshape(q.shape[0], -1), (kp, vp, *state)

    return attend


def _latent_decode(cfg, tables, blks, off, positions, context_lens, layout,
                   block_size, use_kernel, interpret):
    """One latent pool: write the token's row, attend in the absorbed
    form, reading nothing but the rows: through the tables in the
    Pallas latent kernel, else over a dense gather."""
    (blk,), (table,) = blks, tables

    def attend(u, carry, ap, layer):
        (pool,) = carry
        n_layers, n_blocks = pool.shape[:2]
        q_nope, q_rope = _mla.mla_query(u, ap, cfg, positions)
        row = _widen(_mla.mla_latent(u, ap, cfg, positions),
                     pool.shape[-1])
        pool = pool.at[layer, blk, off].set(row.astype(pool.dtype))
        # the whole pool as one run of blocks, this attention's at
        # `layer * n_blocks`: never sliced, never stacked
        rows = pool.reshape((n_layers * n_blocks,) + pool.shape[2:])
        shifted = table + layer * n_blocks
        if use_kernel:
            return (_mla.mla_attend_paged(
                q_nope, q_rope, rows, shifted, context_lens, ap, cfg,
                interpret=interpret), (pool,), None)
        # whole blocks as they lie, in table order (indexing clamps:
        # no out-of-bounds fill pass over the gathered rows)
        ctx = rows[shifted]
        ctx = ctx.reshape(u.shape[0], -1, ctx.shape[-1])  # [B, T, row]
        return (_mla.mla_attend_absorbed(q_nope, q_rope, ctx,
                                         context_lens, ap, cfg),
                (pool,), None)

    return attend


# -- the programs ------------------------------------------------------------

def prefill_step(params, ids, prompt_len, pools, block_table,
                 temperature, top_k, seed, slot=None, *, cfg, model,
                 block_size, latent=False, layout=None,
                 states=("window",)):
    """Causal forward over one block-padded prompt, ids [1, P], for
    the request that will decode in batch row `slot`. Writes all P
    positions' rows through `block_table` (the padded tail lands
    where decode overwrites it before any masked read, or in the
    NULL block), every per-slot state as the prompt's real end
    leaves it into `state[:, slot]`, and samples the first token
    from the last real row. Returns (token [], pools, the model's
    routing counts over the `prompt_len` real tokens)."""
    from ...incubate.nn.ssm import ssd_chunked

    p_len = ids.shape[1]
    positions = jnp.arange(p_len)
    # a table a cache group where the model has several
    tables = (block_table,) if layout is None else tuple(block_table)
    attend, carry, write = (_latent_prefill if latent else _kv_prefill)(
        model, cfg, pools, tables, positions, layout, block_size)

    def window(z, carry, c):
        i = _state_at(states, "window")
        st = carry[i]
        n = st.shape[2]
        zp = jnp.pad(z, ((n, 0), (0, 0)))      # zp[t + n] = z[t]
        win = jnp.stack([zp[j:j + p_len] for j in range(n + 1)], 1)
        tail = _window_tail(zp, prompt_len, n)
        st = st.at[c, slot].set(tail.astype(st.dtype))
        return win, _put(carry, i, st)

    def scan(x, dt, A, B, C, carry, s):
        i = _state_at(states, "ssm")
        st = carry[i]
        with jax.named_scope("ssd"):
            y, last = ssd_chunked(x, dt, A, B, C, length=prompt_len)
        st = st.at[s, slot].set(last.astype(st.dtype))
        return y, _put(carry, i, st)

    x = model.embed(params, ids[0], cfg)
    x, carry, rows, stats = model.decoder_layers(
        params, x, carry, attend, window, scan, positions,
        positions < prompt_len, cfg)
    pools = write(carry, rows)
    last = jax.lax.dynamic_index_in_dim(x, prompt_len - 1, axis=0,
                                        keepdims=False)
    token = sample_tokens(model.logits(params, last, cfg)[None],
                          temperature[None], top_k[None], seed[None])[0]
    return token, pools, stats


def decode_step(params, ids, positions, pools, block_tables,
                context_lens, temperature, top_k, seeds, *, cfg, model,
                block_size, latent=False, use_kernel=False,
                interpret=False, layout=None, states=("window",)):
    """One generation step for the whole running batch, ids and
    positions [B]; `context_lens[b] == positions[b] + 1`. Row b is
    slot b: its per-slot states are `state[:, b]`. Each attention
    writes this token's rows at (tables[b, pos // BS], pos % BS)
    BEFORE attending: with `use_kernel` through the tables in a
    Pallas kernel (`interpret`: under the interpreter, the CPU's
    parity tests), else over a dense gather. Inactive slots (table
    all NULL) ride along and are left out of the routing counts.
    Returns (tokens [B], pools, the model's routing counts)."""
    from ...incubate.nn import pallas as _pl

    # a table a cache group where the model has several: `[groups,
    # B, MAXB]`, indexed by the logical block
    tables = (block_tables,) if layout is None else tuple(block_tables)
    blks = [jnp.take_along_axis(
        t, (positions // block_size)[:, None], axis=1)[:, 0]
        for t in tables]
    off = positions % block_size
    attend = (_latent_decode if latent else _kv_decode)(
        cfg, tables, blks, off, positions, context_lens, layout,
        block_size, use_kernel, interpret)

    def window(z, carry, c):
        i = _state_at(states, "window")
        st = carry[i]
        win = jnp.concatenate([st[c], z[:, None].astype(st.dtype)], 1)
        return win, _put(carry, i, st.at[c].set(win[:, 1:]))

    def scan(x, dt, A, B, C, carry, s):
        from ...incubate.nn.pallas import ssm_state as _ss
        from ...incubate.nn.ssm import ssm_step

        i = _state_at(states, "ssm")
        st = carry[i]
        with jax.named_scope("state"):
            if _ss.ssm_state_supported(st.shape[2], B.shape[-2],
                                       *st.shape[3:], st.dtype):
                y, st = _ss.ssm_state_update(
                    st, s, x, dt, A, B, C, interpret=_pl.interpret_mode())
            else:
                y, new = ssm_step(st[s], x, dt, A, B, C)
                st = st.at[s].set(new)
        return y, _put(carry, i, st)

    x = model.embed(params, ids, cfg)
    x, pools, _, stats = model.decoder_layers(
        params, x, tuple(pools), attend, window, scan, positions,
        tables[0][:, 0] != NULL_BLOCK, cfg)
    tokens = sample_tokens(model.logits(params, x, cfg), temperature,
                           top_k, seeds)
    return tokens, pools, stats


class StateRunner:
    """How LLMEngine serves a model with `decoder_layers`: its cache's
    pools (K and V, or one of latent rows) and per-slot states beside
    them, prefill (told its slot where the model keeps per-slot state)
    and decode (through the paged kernel where `kernel_supported`, the
    state kernel where `scan_kernel`); no verify, tail or draft."""

    verify_step = prefill_tail_step = draft_params = None

    def __init__(self, model):
        model = getattr(model, "model", model)
        self.config = cfg = model.config
        self.params = jax.tree_util.tree_map(
            lambda p: p._value, model._params_tree())
        latent = hasattr(model, "latent_row")
        if latent:
            # one head as wide as the row as stored: padded with zeros
            # to whole 128-lane tiles (576 -> 640 values). With 576 as
            # the minor dimension the TPU stores the pool with the
            # BLOCK axis on the lanes and every program relays it both
            # ways (two 2 GiB copies in the decode program compiled for
            # the v5e, none at 640)
            row = -(-model.latent_row // 128) * 128
            self.heads, self.pool_rows = (1, 1, row), (row,)
        else:
            self.heads = hq, hkv, d = model.kv_heads
            self.pool_rows = (hkv * d,) * 2
        self.pool_layers = model.n_attentions
        self.cache_groups = (None,)
        self.routed_experts = model.routed_experts
        # what the cache allocates: (kind, (layers, *shape), dtype)
        self.slot_state = tuple(getattr(model, "slot_state", ()))
        kinds = tuple(kind for kind, _, _ in self.slot_state)
        # (heads, groups, d_state, head_dim) of the state-space layers,
        # their count and their state's dtype (kind `ssm`)
        self.ssm_heads = getattr(model, "ssm_heads", None)
        ssm = {kind: (shape, dtype)
               for kind, shape, dtype in self.slot_state}.get("ssm")
        self.scan_layers = ssm[0][0] if ssm else 0
        self._ssm_dtype = ssm and ssm[1]
        # the programs read the model's functions, not the instance
        kw = dict(cfg=cfg, model=type(model), latent=latent, states=kinds)
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
        """Does decode attend through a Pallas paged kernel here?
        `paged_decode_supported`'s answer (a TPU, or the interpreter on
        the CPU; no live multi-device mesh; whole 128-lane rows, whole
        sublane groups of a block) for the model's grouped heads, or
        for latent rows ONE shared head as wide as the stored row."""
        from ...incubate.nn.pallas import paged_attention as _pa

        hq, hkv, d = self.heads
        return _pa.paged_decode_supported(hq, d, block_size,
                                          num_kv_heads=hkv)

    def scan_kernel(self):
        """Does the decode program update the state-space layers'
        states in the Pallas kernel here? What that program asked
        while it was traced (`ssm_state.ssm_state_supported`), for the
        model's `ssm_heads`."""
        from ...incubate.nn.pallas import ssm_state as _ss

        return bool(self.scan_layers) and _ss.ssm_state_supported(
            *self.ssm_heads, self._ssm_dtype)

    def experts_kernel(self, tokens):
        """Does a program over `tokens` rows multiply its experts'
        groups in the Pallas grouped matmul here? What that program
        asked while it was traced (`dropless.expert_kernel_
        supported`), for the model's `routed_experts`."""
        return expert_kernel_supported(tokens, *self.routed_experts)
