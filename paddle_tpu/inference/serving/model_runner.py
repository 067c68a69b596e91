"""The runners LLMEngine reads, and GPT-2's paged-serving forward
passes (prefill + single-token decode): the first runner.

A RUNNER is what the engine knows of a model (`runner_for(model)`
picks it from the model's type; nothing else selects it):

    runner.params, runner.config     the raw jnp tree and the config
    runner.pool_rows                 row width of each cache pool
    runner.pool_layers               leading axis of each pool: attentions that keep rows
    runner.slot_state                per-slot arrays beside the pools, `(kind, (layers, *shape), dtype)` each; () for none.
                                     With any, prefill_step takes the request's slot after `seed`
    runner.scan_layers               state-space layers a decode step updates (0: none), and
    runner.scan_kernel()             whether it updates them in the Pallas kernel here
    runner.prefill_step(params, ids, prompt_len, pools, table, temp, top_k, seed, *, block_size)
    runner.decode_step(params, ids, positions, pools, tables, lens, temp, top_k, seeds, *, block_size, use_kernel, interpret)
    runner.verify_step, .prefill_tail_step, .draft_params    or None: no speculation / prefix cache

Every step returns `(tokens, pools, stats)`: `pools` the tuple it
was handed (donated, updated in place), `stats` a dict of small
arrays fetched with the tokens (routing counts; empty for GPT-2).
`GPT2Runner` hands the engine the programs below as they are —
`_pooled` only packs their `k_pool, v_pool` into the tuple, so they
lower to the HLO they always did. `mla_runner.MLARunner` is the
second runner, `state_runner.StateRunner` the third.

The serving engine never calls `GPTModel.forward` — re-running the
full prompt for every generated token is O(S^2) per request. Instead
this module owns the two compiled programs of the generation path:

  * `prefill_step` — ONE causal forward over the (block-padded)
    prompt that also scatters every position's K/V into the paged
    pools through the request's block table, and samples the first
    generated token from the last REAL prompt row.
  * `decode_step`  — one token per running sequence: embed, scan the
    layer stack reading/writing K/V through the pools, ragged paged
    attention over each request's cached context, sample.

`verify_step` (speculative verification) and `prefill_tail_step`
(prefix-cache tail) are decode-shaped too: they write K/V per layer
and attend through the pools.

How the pools go through the layer scan (`_scan_layers_paged`, the
one place the pattern lives): the WHOLE `[L, N, BS, H*D]` pools ride
the scan's CARRY; the scan's `xs` are a layer's weights and its index
`l`. Layer `l` scatters its new K/V rows into the carried pool at
`(l, blk, off)` and attends through the pool viewed as
`[L*N, BS, ...]` (a reshape of leading dimensions) with the block
tables shifted by `l * N`. Nothing slices a layer out of a pool or
stacks one back, so with the pools donated at the jit boundary XLA's
while-loop aliasing updates them in place: the program's output
pools ARE its input buffers, and no op moves a pool-sized or
layer-sized buffer. (A scan that takes the pools as `xs` and returns
them as `ys` moves every layer out and in on every dispatch and
holds a second copy of both pools — `ys` cannot alias `xs`. And on
the TPU a pool whose minor dimension is head_dim alone is stored
with the BLOCK axis on the lanes, so each of those layers is also
transposed both ways: hence `H*D` as one dimension, `kv_cache`.)

All reuse the training model's own math helpers (`_layer_norm`,
`_residual_layer_norm`, `_attention` from `text.models.gpt`) so the
serving path computes EXACTLY what the training forward computes —
the e2e contract is greedy tokens identical to a sequential
full-re-forward loop, and every numerical divergence between the two
paths is a bug, not noise.

Sampling is in-program and per-request: `temperature == 0` is exact
argmax (greedy), `temperature > 0` draws from the (optionally
top-k-filtered) softmax with a seed the HOST derives from (request
seed, absolute token index) — so an evicted-and-re-prefilled request
replays the same random choices it would have made uninterrupted,
whatever batch it lands in.

Functions take the raw jnp parameter tree (`extract_params`), not
Layers: the engine wraps each in a `jit.Program` with the pools
donated.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...text.models.gpt import (_attention, _layer_norm,
                                _residual_layer_norm)

__all__ = ["extract_params", "prefill_step", "decode_step",
           "verify_step", "prefill_tail_step", "draft_params",
           "sample_tokens", "sample_case", "SAMPLE_CASES", "seed_for",
           "GPT2Runner", "runner_for"]


def extract_params(model):
    """(jnp param tree, GPTConfig) from GPTForCausalLM / GPTModel."""
    gpt = getattr(model, "gpt", model)
    tree = gpt._params_tree()
    params = jax.tree_util.tree_map(
        lambda p: p._value if hasattr(p, "_value") else jnp.asarray(p),
        tree)
    return params, gpt.config


def draft_params(params, n_layers):
    """Truncated-layer twin of the target for speculative drafting:
    the first `n_layers` transformer blocks with the embedding /
    final-norm / lm-head weights shared as-is. The draft only has to
    AGREE with the target often enough to pay for its dispatches —
    verification makes the emitted stream the target's own tokens
    regardless of draft quality."""
    if n_layers < 1:
        raise ValueError(f"draft needs >= 1 layer, got {n_layers}")
    total = jax.tree_util.tree_leaves(
        params["blocks"])[0].shape[0]
    if n_layers > total:
        raise ValueError(
            f"draft layers {n_layers} > target layers {total}")
    out = dict(params)
    out["blocks"] = jax.tree_util.tree_map(
        lambda a: a[:n_layers], params["blocks"])
    return out


def seed_for(request_seed, token_index):
    """Host-side per-token sampling seed: a pure function of the
    request's seed and the ABSOLUTE position being sampled, so
    replayed decodes (eviction -> re-prefill) and different batch
    compositions draw identical randomness."""
    return (int(request_seed) * 1000003 + int(token_index)) \
        & 0x7FFFFFFF


def sample_tokens(logits, temperature, top_k, seeds):
    """Per-request next-token selection over [B, V] logits.

    temperature[b] == 0 -> exact argmax (greedy decode);
    temperature[b] > 0  -> categorical over logits/temperature with
    all but the top_k[b] largest masked out when top_k[b] > 0 (ties
    at the k-th place go to the lower token ids). k is per-request
    and traced — `lax.top_k` would force one compiled program per
    distinct k.

    What runs follows what the BATCH asks for, chosen on the device
    by one `lax.switch` on `_batch_case` (`sample_case` on the
    host): no row draws -> the argmax alone; rows draw, none of them
    filters -> the draw too; a drawing row filters -> also the one
    sort that finds each row's k-th largest logit. The switch stands
    outside every `vmap`: under one it would be a select that runs
    all three."""
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    draws = temperature > 0

    def draw_one(lg, t, seed):
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
        return jax.random.categorical(
            key, lg / jnp.maximum(t, 1e-6)).astype(jnp.int32)

    def drawn(lg):
        sampled = jax.vmap(draw_one)(lg, temperature, seeds)
        return jnp.where(draws, sampled, greedy)

    def ranked():
        # the set a stable descending argsort ranks under k: all above
        # the k-th largest value, and of its equals the first by index
        k = jnp.where(top_k > 0, jnp.minimum(top_k, vocab),
                      vocab)[:, None]
        kth = jnp.take_along_axis(
            jnp.sort(logits, axis=-1, stable=False), vocab - k, axis=-1)
        above, ties = logits > kth, logits == kth
        room = k - above.sum(-1, keepdims=True)
        keep = above | (ties & (jnp.cumsum(ties, axis=-1) <= room))
        return drawn(jnp.where(keep, logits, -jnp.inf))

    return jax.lax.switch(
        _batch_case(temperature, top_k),
        (lambda: greedy, lambda: drawn(logits), ranked))


SAMPLE_CASES = ("greedy", "drawn", "ranked")


def _batch_case(temperature, top_k):
    """The index into SAMPLE_CASES of a batch's [B] arrays, on the
    device: two reductions over the batch."""
    draws = temperature > 0
    return jnp.any(draws).astype(jnp.int32) \
        + jnp.any(draws & (top_k > 0)).astype(jnp.int32)


def sample_case(samplings):
    """`_batch_case` on the host, over the batch's SamplingParams:
    the case the program will take, told without a fetch."""
    drawing = [s for s in samplings if s.temperature > 0]
    return (len(drawing) > 0) + any(s.top_k > 0 for s in drawing)


def _scatter_positions(block_table, positions, block_size):
    """(pool block ids, in-block offsets) for a vector of token
    positions resolved through ONE request's block table."""
    return (jnp.take(block_table, positions // block_size, axis=0),
            positions % block_size)


def prefill_step(params, ids, prompt_len, k_pool, v_pool, block_table,
                 temperature, top_k, seed, *, n_head, eps, block_size):
    """Causal forward over one block-padded prompt.

    ids [1, P] (P a multiple of block_size), prompt_len traced scalar.
    Writes all P positions' K/V through `block_table` [MAXB] — padded
    tail positions resolve to slots the decode steps overwrite before
    any masked read could see them, or to the NULL block. Returns
    (first sampled token [], k_pool, v_pool)."""
    p_len = ids.shape[1]
    x = jnp.take(params["wte"], ids, axis=0)
    x = x + jnp.take(params["wpe"], jnp.arange(p_len), axis=0)

    def body(carry, bp):
        h = _layer_norm(carry, bp["ln1_w"], bp["ln1_b"], eps)
        qkv = h @ bp["qkv_w"] + bp["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        # dense causal attention over the prompt itself — the
        # training math, bit-for-bit (no pool read needed: the
        # prompt IS the whole context)
        attn = _attention(q, k, v, n_head, use_flash=False)
        attn = attn @ bp["proj_w"] + bp["proj_b"]
        h2, x2 = _residual_layer_norm(attn, carry, bp["ln2_w"],
                                      bp["ln2_b"], eps)
        ffn = h2 @ bp["fc1_w"] + bp["fc1_b"]
        ffn = jax.nn.gelu(ffn)
        ffn = ffn @ bp["fc2_w"] + bp["fc2_b"]
        out = x2 + ffn
        return out, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    # ks/vs [L, 1, P, H*D] -> scatter every position through the
    # table in one batched update per pool. The layer is an index
    # like blk and off (L x P rows of H*D), not a window dimension:
    # a scatter whose window spans the layer axis makes the TPU
    # compiler transpose the whole pool there and back
    positions = jnp.arange(p_len)
    blk, off = _scatter_positions(block_table, positions, block_size)
    layers = jnp.arange(k_pool.shape[0])[:, None]
    k_pool = k_pool.at[layers, blk, off].set(
        ks[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[layers, blk, off].set(
        vs[:, 0].astype(v_pool.dtype))

    x = _layer_norm(x, params["lnf_w"], params["lnf_b"], eps)
    last = jax.lax.dynamic_index_in_dim(x[0], prompt_len - 1, axis=0,
                                        keepdims=False)
    logits = last @ params["wte"].T                    # [V]
    token = sample_tokens(logits[None], temperature[None],
                          top_k[None], seed[None])[0]
    return token, k_pool, v_pool


def _scan_layers_paged(params, x, k_pool, v_pool, blk, off, attend,
                       *, n_head, eps):
    """The layer stack of a decode-shaped program, with the pools in
    the scan's carry so that they are updated in place.

    x [..., hidden] holds one row per query token; blk/off (shape
    `x.shape[:-1]`) say where each row's K/V goes inside a layer's
    pool. Layer `l` writes its rows at `(l, blk, off)` of the carried
    `[L, N, BS, H*D]` pools BEFORE attending, then calls

        attend(q [..., H, D], k_flat, v_flat, first_block) -> [..., H, D]

    with the pools viewed as `[L*N, BS, H, D]` and `first_block ==
    l * N`: the caller looks its block tables up at `tables +
    first_block`, through the dense reference or the Pallas kernel
    alike (its copies resolve one block id a page, and both read a
    block as `[BS, H*D]`, so the view's split of the minor dimension
    folds away). No layer is sliced out or stacked
    back. L and N come from the pools handed in (the target's, or
    the shallower draft's). Returns (x after the last layer, k_pool,
    v_pool)."""
    n_layers, n_blocks, block_size = k_pool.shape[:3]
    heads = (n_head, x.shape[-1] // n_head)
    flat = (n_layers * n_blocks, block_size) + heads

    def body(carry, xs):
        x, kp, vp = carry
        bp, layer = xs
        h = _layer_norm(x, bp["ln1_w"], bp["ln1_b"], eps)
        qkv = h @ bp["qkv_w"] + bp["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        kp = kp.at[layer, blk, off].set(k.astype(kp.dtype))
        vp = vp.at[layer, blk, off].set(v.astype(vp.dtype))
        attn = attend(q.reshape(x.shape[:-1] + heads),
                      kp.reshape(flat), vp.reshape(flat),
                      layer * n_blocks)
        attn = attn.reshape(x.shape)
        attn = attn @ bp["proj_w"] + bp["proj_b"]
        h2, x2 = _residual_layer_norm(attn, x, bp["ln2_w"],
                                      bp["ln2_b"], eps)
        ffn = h2 @ bp["fc1_w"] + bp["fc1_b"]
        ffn = jax.nn.gelu(ffn)
        ffn = ffn @ bp["fc2_w"] + bp["fc2_b"]
        return (x2 + ffn, kp, vp), None

    (x, k_pool, v_pool), _ = jax.lax.scan(
        body, (x, k_pool, v_pool),
        (params["blocks"], jnp.arange(n_layers, dtype=jnp.int32)))
    return x, k_pool, v_pool


def decode_step(params, ids, positions, k_pool, v_pool, block_tables,
                context_lens, temperature, top_k, seeds, *, n_head,
                eps, block_size, use_kernel=False, interpret=False):
    """One generation step for the whole running batch.

    ids/positions [B]; context_lens[b] == positions[b] + 1 (this
    token included). Each layer writes this token's K/V at
    (tables[b, pos // BS], pos % BS) BEFORE attending — so the
    current token sees itself, and garbage a block-padded prefill
    left in that slot is overwritten before any read. The pools ride
    the layer scan's carry (`_scan_layers_paged`): donated, they are
    updated in place. Returns (next tokens [B], k_pool, v_pool)."""
    from ...incubate.nn.pallas import paged_attention as _pa

    scale = 1.0 / math.sqrt(params["wte"].shape[1] // n_head)
    x = jnp.take(params["wte"], ids, axis=0)
    x = x + jnp.take(params["wpe"], positions, axis=0)

    blk = jnp.take_along_axis(
        block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
    off = positions % block_size

    def attend(q, k_flat, v_flat, first_block):
        tables = block_tables + first_block
        if use_kernel:
            return _pa.paged_attention(q, k_flat, v_flat, tables,
                                       context_lens, sm_scale=scale,
                                       interpret=interpret)
        return _pa.paged_attention_reference(
            q, k_flat, v_flat, tables, context_lens, sm_scale=scale)

    x, k_pool, v_pool = _scan_layers_paged(
        params, x, k_pool, v_pool, blk, off, attend, n_head=n_head,
        eps=eps)
    x = _layer_norm(x, params["lnf_w"], params["lnf_b"], eps)
    logits = x @ params["wte"].T                       # [B, V]
    tokens = sample_tokens(logits, temperature, top_k, seeds)
    return tokens, k_pool, v_pool


def verify_step(params, ids, start_positions, k_pool, v_pool,
                block_tables, context_lens, temperature, top_k,
                seeds, *, n_head, eps, block_size, use_kernel=False,
                interpret=False):
    """Speculative-decode verification: T tokens per sequence in ONE
    fixed-shape dispatch.

    ids [B, T]: slot 0 is the sequence's pending token (sampled last
    round, K/V unwritten), slots 1..T-1 the draft proposals. Token
    (b, t) sits at absolute position `start_positions[b] + t` and
    `context_lens[b] == start_positions[b] + 1` (slot 0 inclusive).
    Each layer writes all T slots' K/V through the table BEFORE the
    multi-query paged attention, so slot t sees slots 0..t (and
    nothing deeper — per-slot causal masking). Returns
    (tokens [B, T], k_pool, v_pool): tokens[b, t] is the target's
    choice for output index context_lens[b] + t, sampled with
    seeds[b, t] — the SAME position-keyed seed the k=1 engine would
    use, which is what makes acceptance token-identical for any
    temperature. Rejected slots' K/V writes land at positions beyond
    the accepted context and are overwritten by a later dispatch
    before any masked read could see them. `block_tables` may carry
    a trailing guaranteed-NULL column: positions past the table's
    real width clamp into it, so an at-cap sequence's overflow slots
    write garbage to the NULL block instead of its own live tail.
    The pools ride the layer scan's carry (`_scan_layers_paged`),
    as in `decode_step`."""
    from ...incubate.nn.pallas import paged_attention as _pa

    bsz, t_q = ids.shape
    scale = 1.0 / math.sqrt(params["wte"].shape[1] // n_head)
    positions = start_positions[:, None] \
        + jnp.arange(t_q)[None, :]                     # [B, T]
    x = jnp.take(params["wte"], ids, axis=0)
    x = x + jnp.take(params["wpe"], positions, axis=0)

    maxb = block_tables.shape[1]
    slot_idx = jnp.minimum(positions // block_size, maxb - 1)
    blk = jnp.take_along_axis(block_tables, slot_idx, axis=1)
    off = positions % block_size

    def attend(q, k_flat, v_flat, first_block):
        tables = block_tables + first_block
        if use_kernel:
            return _pa.paged_attention_multi(
                q, k_flat, v_flat, tables, context_lens,
                sm_scale=scale, interpret=interpret)
        return _pa.paged_attention_multi_reference(
            q, k_flat, v_flat, tables, context_lens, sm_scale=scale)

    x, k_pool, v_pool = _scan_layers_paged(
        params, x, k_pool, v_pool, blk, off, attend, n_head=n_head,
        eps=eps)
    x = _layer_norm(x, params["lnf_w"], params["lnf_b"], eps)
    logits = x @ params["wte"].T                       # [B, T, V]
    vocab = logits.shape[-1]
    flat = sample_tokens(
        logits.reshape(bsz * t_q, vocab),
        jnp.repeat(temperature, t_q), jnp.repeat(top_k, t_q),
        seeds.reshape(bsz * t_q))
    return flat.reshape(bsz, t_q), k_pool, v_pool


def prefill_tail_step(params, ids, start, total_len, k_pool, v_pool,
                      block_table, temperature, top_k, seed, *,
                      n_head, eps, block_size):
    """Prefix-cache tail prefill: causal forward over ONLY the
    uncached tail of one request's context.

    The leading `start` tokens (a multiple of block_size) already
    have their K/V in the pools through shared table blocks; ids
    [1, Tpad] holds the tail (block-padded), whose token t sits at
    absolute position `start + t`. Each layer writes the tail's K/V
    through the table, then attends over the WHOLE paged context via
    the multi-query reference (slot t sees start + t + 1 tokens).
    Samples from the last REAL tail row (`total_len - 1 - start`).
    The tail is never empty — the engine caps sharing below the full
    context, so the sampling row always exists. The pools ride the
    layer scan's carry (`_scan_layers_paged`), as in `decode_step`.
    Returns (first sampled token [], k_pool, v_pool)."""
    from ...incubate.nn.pallas import paged_attention as _pa

    t_pad = ids.shape[1]
    scale = 1.0 / math.sqrt(params["wte"].shape[1] // n_head)
    positions = start + jnp.arange(t_pad)
    x = jnp.take(params["wte"], ids, axis=0)
    x = x + jnp.take(params["wpe"], positions, axis=0)[None]

    blk, off = _scatter_positions(block_table, positions, block_size)

    def attend(q, k_flat, v_flat, first_block):
        # dense multi-query reference (T can be a whole prompt tail —
        # too long for the unrolled kernel): slot t's context is
        # (start + 1) + t tokens, cached prefix included
        return _pa.paged_attention_multi_reference(
            q, k_flat, v_flat, (block_table + first_block)[None],
            jnp.asarray([start + 1]), sm_scale=scale)

    x, k_pool, v_pool = _scan_layers_paged(
        params, x, k_pool, v_pool, blk[None], off[None], attend,
        n_head=n_head, eps=eps)
    x = _layer_norm(x, params["lnf_w"], params["lnf_b"], eps)
    last = jax.lax.dynamic_index_in_dim(
        x[0], total_len - 1 - start, axis=0, keepdims=False)
    logits = last @ params["wte"].T                    # [V]
    token = sample_tokens(logits[None], temperature[None],
                          top_k[None], seed[None])[0]
    return token, k_pool, v_pool


# -- the runner ----------------------------------------------------------------

def _pooled(step, at):
    """`step(..., k_pool, v_pool, ...)` behind the engine's calling
    convention: the pools are ONE argument (a tuple, at position
    `at`) and the result is `(tokens, pools, stats)`. Flattened, the
    arguments and results are what `step` itself has."""
    def run(*args, **kw):
        out = step(*args[:at], *args[at], *args[at + 1:], **kw)
        return out[0], tuple(out[1:]), {}
    return run


class GPT2Runner:
    """How LLMEngine serves a GPTForCausalLM / GPTModel: a K and a V
    pool of `hidden_size` values a token a layer, and the four
    programs above."""

    draft_params = staticmethod(draft_params)
    slot_state = ()
    scan_layers = 0

    def __init__(self, model):
        self.params, self.config = extract_params(model)
        c = self.config
        kw = dict(n_head=c.num_heads, eps=c.layer_norm_eps)
        self.pool_rows = (c.hidden_size,) * 2
        self.pool_layers = c.num_layers
        self.prefill_step = functools.partial(
            _pooled(prefill_step, 3), **kw)
        self.decode_step = functools.partial(
            _pooled(decode_step, 3), **kw)
        self.verify_step = functools.partial(
            _pooled(verify_step, 3), **kw)
        self.prefill_tail_step = functools.partial(
            _pooled(prefill_tail_step, 4), **kw)

    def kernel_supported(self, block_size):
        """Does the Pallas paged-attention kernel take this model's
        heads at this block size, here?"""
        from ...incubate.nn.pallas import paged_attention as _pa

        c = self.config
        return _pa.paged_decode_supported(
            c.num_heads, c.hidden_size // c.num_heads, block_size)


def runner_for(model):
    """The runner of a model: `MLARunner` for one that says what its
    latent-attention layers are (`mla_layers`), `StateRunner` for
    one whose layers keep per-slot state beside keys and values
    (`state_layers`), else GPT-2's."""
    inner = getattr(model, "model", model)
    if hasattr(inner, "mla_layers"):
        from .mla_runner import MLARunner

        return MLARunner(model)
    if hasattr(inner, "state_layers"):
        from .state_runner import StateRunner

        return StateRunner(model)
    return GPT2Runner(model)
