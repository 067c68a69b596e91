"""The third runner: models that keep TWO kinds of state a sequence
— keys and values in paged pools, addressed through block tables,
and beside them states of fixed size (the tail of a short
convolution's window: `text/models/lfm2_moe.py`; the state of a
state-space recurrence: `text/models/falcon_h1.py`), addressed by the
sequence's SLOT in the decode batch.

What a layer is the runner reads from the model and names no model:
`model.embed(params, ids, cfg)` gives the first hidden rows,
`model.state_layers(params, x, carry, attend, window, scan,
positions, live, cfg)` runs the model's own layers and calls the
program's

    attend(q [T, Hq, D], k [T, Hkv*D], v [T, Hkv*D], carry, a)
    window(z [T, H], carry, c) -> (z_{t-n} .. z_t [T, n + 1, H], carry)
    scan(x [T, H, P], dt [T, H], A [H], B [T, G, N], C [T, G, N],
         carry, s) -> (y [T, H, P] float32, carry)

once for every attention `a`, every windowed layer `c` and every
state-space layer `s` (`incubate/nn/ssm.py` has the recurrence;
`y` without the model's skip term); `model.n_attentions` is the K/V
pools' layer count, `model.kv_heads` the heads (query heads may be a
multiple of the K/V heads: the pools hold the K/V heads' rows, `Hkv *
D` a token), `model.slot_state` the per-slot arrays, each `(kind,
(layers, *shape a layer), dtype)` (dtype None: the pools'): kind
`window` the last `n` rows of every windowed layer's stream `(layers,
n, width)`, kind `ssm` every state-space layer's state `(layers, H, N,
P)`. So the serving path is the training mathematics.

The carry is the engine's `pools`: the K and V pools `[A, N, BS,
Hkv*D]` and after them the per-slot arrays `[layers, max_batch,
*shape]` in the order the model declares them (`kv_cache.
PagedKVCache`), donated and updated in place: rows scattered at `(a,
blk, off)` and read as `[A*N, ...]` with the tables shifted by `a *
N` (`model_runner._scan_layers_paged`'s rule; the layers are
unrolled, `a`, `c` and `s` are Python numbers); a callback finds its
array in the carry by its kind.

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
  through the block table, and writes each per-slot state AT THE
  PROMPT'S END into the request's slot (not at the padded bucket's
  end): a window's tail `z[prompt_len - n : prompt_len]` (zeros
  before position 0), a state-space layer's state after position
  `prompt_len - 1` (`ssm.ssd_chunked` from a zero state, under the
  scope `ssd`, the padded tail given dt = 0). `slot` is one more
  argument, after `seed`.
- `decode_step`: the batch row IS the slot. A windowed layer reads
  its `[B, n, H]` state, appends this token's row, drops the oldest
  and writes the state back whole, in place, with no gather; a
  state-space layer updates its `[B, H, N, P]` state in place, under
  the scope `state`: in the Pallas kernel (`pallas/ssm_state.py`, one
  pass over the state) where `ssm_state_supported` says so (a TPU),
  else `ssm.ssm_step`. Inactive slots ride along and whatever they
  leave is overwritten whole by the next prefill into the slot. An
  attention writes the token's K and V rows and attends through the
  tables: in the Pallas paged kernel with grouped heads where
  `kernel_supported` says so (a TPU), else over a dense gather (the
  CPU's path, and the reference the kernel is tested against).

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


def _state_at(states, kind):
    """Where the per-slot array of `kind` is in the carry: behind the
    K and V pools, in the order the model declares them."""
    return 2 + states.index(kind)


def _put(carry, i, value):
    return carry[:i] + (value,) + carry[i + 1:]


def prefill_step(params, ids, prompt_len, pools, block_table,
                 temperature, top_k, seed, slot=None, *, cfg, model,
                 block_size, layout=None, states=("window",)):
    """Causal forward over one block-padded prompt, ids [1, P], for
    the request that will decode in batch row `slot`. Writes all P
    positions' K/V rows through `block_table` (the padded tail lands
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
    x, pools, stats = model.state_layers(
        params, x, tuple(pools), attend, window, scan, positions,
        positions < prompt_len, cfg)
    last = jax.lax.dynamic_index_in_dim(x, prompt_len - 1, axis=0,
                                        keepdims=False)
    token = sample_tokens(model.logits(params, last, cfg)[None],
                          temperature[None], top_k[None], seed[None])[0]
    return token, pools, stats


def decode_step(params, ids, positions, pools, block_tables,
                context_lens, temperature, top_k, seeds, *, cfg, model,
                block_size, use_kernel=False, interpret=False,
                layout=None, states=("window",)):
    """One generation step for the whole running batch, ids and
    positions [B]; `context_lens[b] == positions[b] + 1`. Row b is
    slot b: its per-slot states are `state[:, b]`. Each attention
    writes this token's K/V rows at (tables[b, pos // BS], pos % BS)
    BEFORE attending. Inactive slots (table all NULL) ride along and
    are left out of the routing counts. Returns (tokens [B], pools,
    the model's routing counts)."""
    from ...incubate.nn import pallas as _pl
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
    x, pools, stats = model.state_layers(
        params, x, tuple(pools), attend, window, scan, positions,
        group_tables[0][:, 0] != NULL_BLOCK, cfg)
    tokens = sample_tokens(model.logits(params, x, cfg), temperature,
                           top_k, seeds)
    return tokens, pools, stats


class StateRunner:
    """How LLMEngine serves a model with `state_layers`: a K and a V
    pool of `Hkv * D` values a token an attention, per-slot states
    beside them, prefill (told its slot) and decode (through the
    paged kernel where `kernel_supported`, the state kernel where
    `scan_kernel`); no verify, tail or draft."""

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
        self.routed_experts = getattr(model, "routed_experts", None)
        # what the cache allocates: (kind, (layers, *shape), dtype)
        self.slot_state = tuple(model.slot_state)
        kinds = tuple(kind for kind, _, _ in self.slot_state)
        # (heads, groups, d_state, head_dim) of the state-space layers,
        # their count and their state's dtype (kind `ssm`)
        self.ssm_heads = getattr(model, "ssm_heads", None)
        ssm = {kind: (shape, dtype)
               for kind, shape, dtype in self.slot_state}.get("ssm")
        self.scan_layers = ssm[0][0] if ssm else 0
        self._ssm_dtype = ssm and ssm[1]
        # the programs read the model's functions, not the instance
        kw = dict(cfg=cfg, model=type(model), states=kinds)
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
