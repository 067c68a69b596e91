"""Pallas TPU ragged paged-attention decode kernel.

The serving-side sibling of `attention_pallas.py` (PR 8): that kernel
streams contiguous K/V tiles for TRAINING-shaped batches; this one
reads K/V through per-request BLOCK TABLES out of the paged pools
(`inference.serving.kv_cache`), so ONE launch covers every sequence
in a continuous-batching decode step at mixed context lengths — the
Ragged Paged Attention design (PAPERS.md arxiv 2604.15464).

Decode shape: one query token per sequence.

    q            [B, H, D]           this step's query rows
    k/v pool     [N, BS, H, D]       a paged pool: one layer's, or every
                                     layer's stacked on the block axis
                                     with the tables shifted to the
                                     layer (serving.model_runner)
    block_tables [B, MAXB] int32     pool block id per (seq, slot)
    context_lens [B]       int32     real tokens per sequence

Multi-query decode (`paged_attention_multi`): the speculative-decode
verify dispatch feeds T consecutive query tokens per sequence — q is
[B, T, H, D], query slot `t` of sequence `b` sits at absolute
position `context_lens[b] - 1 + t` and may attend over
`context_lens[b] + t` tokens (itself included). Same grid, same
block streaming: the per-slot causal offset is a compile-time
constant (the T-loop is python-unrolled, T <= 8), so one launch
verifies a whole draft window per sequence with per-slot position
masking instead of T separate dispatches.

Grid: (B, MAXB). `block_tables`/`context_lens` ride as SCALAR
PREFETCH arguments (pltpu.PrefetchScalarGridSpec) so the K/V
BlockSpec index maps resolve `tables[b, j]` BEFORE the kernel body —
the DMA engine fetches exactly the blocks each sequence owns, in
table order, nothing else. Dead blocks (slots past the sequence's
context length) are grid-skipped with `pl.when`, the pad-and-mask
discipline the PR-8 flash kernel established: a fully-dead block
costs its (skipped) grid step, never a matmul; the tail block masks
`k_pos >= context_len` scores to -inf so padded slots contribute
exactly zero weight. Online softmax (running max/denominator in VMEM
scratch) accumulates across a sequence's blocks, so nothing
[S, S]-shaped ever materializes.

Layout inside the kernel: everything is 2-D and lane-dense. A pool
block is read as [BS, H*D] (the pool's own memory order, no
transpose) and each head's D lanes are addressed through two small
0/1 matrices on the MXU instead of 3-D reshapes, which Mosaic refuses
("infer-vector-layout: unsupported shape cast ... vector<1x16x64xbf16>
-> vector<16x1x64xbf16>" — the first version's `q[:, None, :]` and
its batched M=1 dot_general):

    scores   [BS, H]   = K[BS, H*D] @ Qx[H*D, H]     Qx block-diagonal:
                         column h holds head h's query on its D rows
    weights  [BS, H*D] = P[BS, H] @ SEL[H, H*D]      SEL row h is 1 on
                         head h's D lanes
    output   [1, H*D] += sum over BS of weights * V[BS, H*D]

`interpret=True` runs the same kernel through the Pallas interpreter
for CPU parity tests (the PR-8 contract; see
`paged_attention_reference` for the dense gather it must match).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_attention_multi", "paged_attention_multi_reference",
           "paged_decode_supported"]

_NEG_INF = -1e30


def paged_decode_supported(num_heads, head_dim, block_size):
    """Can the compiled TPU kernels (decode and multi-query verify)
    take this geometry here? A pool block is one [BS, H*D] tile, so
    H*D must fill whole 128-lane rows and BS whole sublane groups;
    the interpreter (CPU parity) takes anything."""
    from . import interpret_mode, kernels_available

    if not kernels_available():
        return False
    if interpret_mode():
        return True
    return (num_heads * head_dim) % 128 == 0 and block_size % 8 == 0


def _head_selector(h, d, dtype):
    """SEL [H, H*D]: row h is 1 on head h's D lanes, 0 elsewhere."""
    return jnp.repeat(jnp.eye(h, dtype=dtype), d, axis=1)


def _block_diag_q(q, sel):
    """q [..., H, D] -> Qx [..., H*D, H]: column h is head h's query
    on its own D rows (a 0/1 mask, exact in any dtype)."""
    h, d = q.shape[-2:]
    flat = q.reshape(q.shape[:-2] + (h * d, 1))
    return flat * sel.T.astype(q.dtype)


def _over_lanes(x, sel, pieces):
    """Per-head values x [R, H] f32 -> [R, H*D] on each head's lanes,
    exactly: x goes through the 0/1 selector on the MXU as `pieces`
    successive bf16 roundings of its remainder (a bf16 x 1 product
    and the f32 sum of one term per lane are exact; three pieces
    carry all 24 mantissa bits of an f32)."""
    out = None
    for _ in range(pieces):
        piece = x.astype(sel.dtype)
        term = jnp.dot(piece, sel, preferred_element_type=jnp.float32)
        out = term if out is None else out + term
        x = x - piece.astype(jnp.float32)
    return out


def _slot_update(s, ctx, j, block_size, v, sel, m_prev, l_prev, acc_prev):
    """One online-softmax step for one query slot over one KV block.
    s [BS, H] f32 scaled scores; returns the new (m, l, acc)."""
    k_pos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    s = jnp.where(k_pos < ctx, s, _NEG_INF)
    m_cur = jnp.max(s, axis=0, keepdims=True)              # [1, H]
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                 # [BS, H]
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=0, keepdims=True)
    # operands stay in the pool dtype (bf16-native MXU), statistics
    # f32 (the PR-8 rule): p is rounded to the pool dtype exactly as
    # the reference rounds it before its PV product
    pv = p.astype(v.dtype)
    w = _over_lanes(pv.astype(jnp.float32), sel,
                    1 if v.dtype == jnp.bfloat16 else 3)   # [BS, H*D]
    acc_new = acc_prev * _over_lanes(alpha, sel, 3) + jnp.sum(
        w * v.astype(jnp.float32), axis=0, keepdims=True)  # [1, H*D]
    return m_new, l_new, acc_new


def _paged_kernel(tables_ref, lens_ref, qx_ref, sel_ref, k_ref, v_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, sm_scale, block_size,
                  num_slots):
    b = pl.program_id(0)
    j = pl.program_id(1)
    ctx = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # grid-skip dead blocks: table slots at or past the context hold
    # NULL_BLOCK padding — no matmul, no softmax update
    @pl.when(j * block_size < ctx)
    def _step():
        s = jnp.dot(k_ref[0], qx_ref[0],
                    preferred_element_type=jnp.float32) * sm_scale
        m_ref[...], l_ref[...], acc_ref[...] = _slot_update(
            s, ctx, j, block_size, v_ref[0], sel_ref[...],
            m_ref[...], l_ref[...], acc_ref[...])

    @pl.when(j == num_slots - 1)
    def _finish():
        l = _over_lanes(jnp.maximum(l_ref[...], 1e-30), sel_ref[...], 3)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _check_pool(q_heads_dim, k_pool):
    hk, dk = k_pool.shape[2:]
    if (hk, dk) != q_heads_dim:
        raise ValueError(
            f"pool heads/dim {(hk, dk)} != query {q_heads_dim}")


def paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                    sm_scale=1.0, interpret=False):
    """Ragged paged-attention decode: one launch, all sequences."""
    b, h, d = q.shape
    n, bs = k_pool.shape[:2]
    _check_pool((h, d), k_pool)
    hd = h * d
    maxb = block_tables.shape[1]
    sel = _head_selector(h, d, jnp.bfloat16)
    # scores run on the pool's dtype (bf16-native MXU; an f32 pool
    # keeps f32 scores)
    qx = _block_diag_q(q.astype(k_pool.dtype), sel)        # [B, HD, H]
    kernel = functools.partial(
        _paged_kernel, sm_scale=sm_scale, block_size=bs,
        num_slots=maxb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxb),
        in_specs=[
            pl.BlockSpec((1, hd, h), lambda i, j, bt, cl: (i, 0, 0)),
            pl.BlockSpec((h, hd), lambda i, j, bt, cl: (0, 0)),
            pl.BlockSpec((1, bs, hd),
                         lambda i, j, bt, cl: (bt[i, j], 0, 0)),
            pl.BlockSpec((1, bs, hd),
                         lambda i, j, bt, cl: (bt[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd),
                               lambda i, j, bt, cl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(context_lens, jnp.int32), qx, sel,
      k_pool.reshape(n, bs, hd), v_pool.reshape(n, bs, hd))
    return out.reshape(b, h, d)


def _gather_context(pool, block_tables):
    """pool [N, BS, H, D] -> every sequence's context [B, MAXB*BS, H,
    D] in table order. Blocks are gathered as whole [BS, H*D] rows,
    the pools' own minor dimension (`kv_cache`), so a pool handed in
    as a view of that form is read where it lies."""
    n, bs, h, d = pool.shape
    b, maxb = block_tables.shape
    return pool.reshape(n, bs, h * d)[block_tables].reshape(
        b, maxb * bs, h, d)


def paged_attention_reference(q, k_pool, v_pool, block_tables,
                              context_lens, sm_scale=1.0):
    """Dense gather reference — the math the kernel must match, and
    the engine's CPU fallback. Mirrors the training `_attention`
    softmax exactly (f32 scores, -1e30 mask, softmax, cast, PV) so a
    paged decode step reproduces the full re-forward loop's tokens."""
    seq_k = _gather_context(k_pool, block_tables)
    seq_v = _gather_context(v_pool, block_tables)
    s = jnp.einsum("bhd,bshd->bhs", q, seq_k,
                   preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.arange(seq_k.shape[1])[None, :] < context_lens[:, None]
    s = jnp.where(mask[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bshd->bhd", p, seq_v)


# ---------------------------------------------------------------------------
# multi-query decode slots (speculative-decode verification)
# ---------------------------------------------------------------------------

def _paged_multi_kernel(tables_ref, lens_ref, qx_ref, sel_ref, k_ref,
                        v_ref, o_ref, acc_ref, m_ref, l_ref, *, sm_scale,
                        block_size, num_slots, num_q):
    """Per (sequence, table slot) grid step over T query slots. The
    scratch holds one row of online-softmax state per slot; the
    T-loop is python-unrolled so every per-slot causal offset is a
    constant."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    ctx0 = lens_ref[b]               # tokens visible to query slot 0

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # the deepest slot sees ctx0 + num_q - 1 tokens; blocks past that
    # are dead for EVERY slot and grid-skip like the single-query
    # kernel. Shallower slots mask the block's tail per-position: a
    # block entirely past a slot's context masks to all -inf, p
    # underflows to zero and alpha to one, so that slot's state
    # passes through untouched.
    @pl.when(j * block_size < ctx0 + num_q - 1)
    def _step():
        k = k_ref[0]
        v = v_ref[0]
        sel = sel_ref[...]
        for t in range(num_q):
            s = jnp.dot(k, qx_ref[0, t],
                        preferred_element_type=jnp.float32) * sm_scale
            row = slice(t, t + 1)
            m_ref[row], l_ref[row], acc_ref[row] = _slot_update(
                s, ctx0 + t, j, block_size, v, sel,
                m_ref[row], l_ref[row], acc_ref[row])

    @pl.when(j == num_slots - 1)
    def _finish():
        l = _over_lanes(jnp.maximum(l_ref[...], 1e-30), sel_ref[...], 3)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_multi(q, k_pool, v_pool, block_tables,
                          context_lens, sm_scale=1.0,
                          interpret=False):
    """Multi-query ragged paged-attention: q [B, T, H, D], slot t of
    sequence b attends `context_lens[b] + t` tokens (per-slot causal
    masking over the SAME block table). One launch verifies a whole
    speculative window; T must be small (the slot loop unrolls)."""
    b, t, h, d = q.shape
    if t > 8:
        raise ValueError(
            f"paged_attention_multi unrolls the slot loop — T={t} "
            "query slots > 8 would bloat the kernel; use the dense "
            "reference for long windows")
    n, bs = k_pool.shape[:2]
    _check_pool((h, d), k_pool)
    hd = h * d
    maxb = block_tables.shape[1]
    sel = _head_selector(h, d, jnp.bfloat16)
    qx = _block_diag_q(q.astype(k_pool.dtype), sel)     # [B, T, HD, H]
    kernel = functools.partial(
        _paged_multi_kernel, sm_scale=sm_scale, block_size=bs,
        num_slots=maxb, num_q=t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxb),
        in_specs=[
            pl.BlockSpec((1, t, hd, h),
                         lambda i, j, bt, cl: (i, 0, 0, 0)),
            pl.BlockSpec((h, hd), lambda i, j, bt, cl: (0, 0)),
            pl.BlockSpec((1, bs, hd),
                         lambda i, j, bt, cl: (bt[i, j], 0, 0)),
            pl.BlockSpec((1, bs, hd),
                         lambda i, j, bt, cl: (bt[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, hd),
                               lambda i, j, bt, cl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((t, hd), jnp.float32),
            pltpu.VMEM((t, h), jnp.float32),
            pltpu.VMEM((t, h), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, t, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(context_lens, jnp.int32), qx, sel,
      k_pool.reshape(n, bs, hd), v_pool.reshape(n, bs, hd))
    return out.reshape(b, t, h, d)


def paged_attention_multi_reference(q, k_pool, v_pool, block_tables,
                                    context_lens, sm_scale=1.0):
    """Dense multi-query reference: the verify math the kernel must
    match, the engine's CPU fallback, AND the prefix-cache tail
    prefill's attention (slot t at absolute position
    context_lens[b] - 1 + t sees context_lens[b] + t tokens — the
    same convention for both uses)."""
    seq_k = _gather_context(k_pool, block_tables)
    seq_v = _gather_context(v_pool, block_tables)
    t = q.shape[1]
    s = jnp.einsum("bthd,bshd->bths", q, seq_k,
                   preferred_element_type=jnp.float32) * sm_scale
    pos = jnp.arange(seq_k.shape[1])[None, None, :]
    ctx = context_lens[:, None, None] \
        + jnp.arange(t)[None, :, None]     # [B, T, 1]
    mask = pos < ctx                       # [B, T, S]
    s = jnp.where(mask[:, :, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bths,bshd->bthd", p, seq_v)
