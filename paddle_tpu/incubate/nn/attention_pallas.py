"""Pallas TPU flash-attention kernels (forward AND backward).

Replaces the reference's fused CUDA attention
(paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h) with
TPU-native tiled kernels: online-softmax over KV tiles streamed through
VMEM, so neither the [S, S] score matrix nor full K/V ever sit in VMEM
at once; QK^T and PV ride the MXU with fp32 accumulation.

- forward: grid (batch*heads, q_blocks, kv_blocks); KV tiles are
  streamed per grid step (block shape (1, block_k, d)) and the output
  accumulator/running-max/denominator live in VMEM scratch. The
  logsumexp per query row is written out for the backward pass.
- backward: two kernels. dq iterates (bh, q_blocks, kv_blocks)
  accumulating dq in scratch; dk/dv iterates (bh, kv_blocks, q_blocks)
  accumulating dk and dv. Both recompute probabilities from q,k and the
  saved logsumexp — the standard flash-attention backward, O(S) memory.
- `interpret=True` runs the same kernels through the Pallas interpreter
  so correctness is testable on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# r5 on-chip sweep (benchmarks/attn_bench.py, B=4 H=16 S=1024 D=64,
# fwd+bwd): (1024,1024) 1.22 ms beats (256,256) 2.50 ms, (512,512)
# 3.40 ms, jax's reference TPU pallas kernel 4.48 ms and XLA dense
# 8.25 ms — per-grid-step overhead dominates KV streaming at these
# sizes, so prefer the largest block that fits VMEM (the [bq,bk] f32
# score tile is the biggest buffer: 1024^2*4 = 4 MB of ~16 MB).
# _pick_block still drops to divisors of shorter sequences, and long
# sequences tile at 1024 with the causal block skip.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024

# per-row stats (lse/delta) ride a trailing lane dim; 8 satisfies the
# TPU tiling rule (block last dim == full array dim) at 16x less HBM
# than the 128-lane layout
_STAT_LANES = 8


def _pick_block(seq, preferred):
    """Largest power-of-two block <= preferred that divides seq, or
    None when the sequence needs padding (no pow2 divisor >= 8)."""
    for cand in (preferred, 512, 256, 128, 64, 32, 16, 8):
        if cand <= preferred and cand <= seq and seq % cand == 0:
            return cand
    return None


def _block_and_pad(seq, preferred):
    """(block, padded_seq). Divisor-free lengths (a 129-token prompt,
    a ragged tail microbatch) pad UP to the next multiple of the
    largest power-of-two block <= min(preferred, seq): the kernels
    mask padded KV positions to -inf (exactly zero attention weight)
    and padded q rows are sliced off, so the unpadded region is
    bit-identical to an unpadded run — see _mask_scores."""
    b = _pick_block(seq, preferred)
    if b is not None:
        return b, seq
    b = 8
    while b * 2 <= min(preferred, seq):
        b *= 2
    return b, ((seq + b - 1) // b) * b

_NEG_INF = -1e30


def _mask_scores(s, qi, ki, block_q, block_k, causal, kv_len):
    """Causal and/or padded-KV masking of one score tile. kv_len is
    the REAL key length; positions >= kv_len are padding and score
    -inf (exp underflows to exactly 0 — padded keys contribute
    nothing, bit-exactly). kv_len=None means no padding."""
    if not causal and kv_len is None:
        return s
    bq, bk = s.shape
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        ok = q_pos >= k_pos
        if kv_len is not None:
            ok = jnp.logical_and(ok, k_pos < kv_len)
    else:
        ok = k_pos < kv_len
    return jnp.where(ok, s, _NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_ref, m_ref, l_ref, *,
                   sm_scale, causal, block_q, block_k, num_kv,
                   kv_len=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: blocks strictly above the diagonal contribute nothing;
    # fully-padded KV blocks (past the real key length) likewise
    run = (qi + 1) * block_q > ki * block_k if causal else True
    if kv_len is not None:
        kv_run = ki * block_k < kv_len
        run = kv_run if run is True else jnp.logical_and(run, kv_run)

    @pl.when(run)
    def _step():
        # keep matmul OPERANDS in the input dtype (bf16): the MXU is
        # bf16-native with f32 accumulation — casting q/k/v up to f32
        # before the dots ran the matmuls on the slow f32 path (r5).
        # Softmax statistics stay f32 (preferred_element_type).
        q = q_ref[0]                                      # [BQ, D]
        k = k_ref[0]                                      # [BK, D]
        v = v_ref[0]                                      # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        s = _mask_scores(s, qi, ki, block_q, block_k, causal, kv_len)
        m_prev = m_ref[:, :1]                             # [BQ, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_kv - 1)
    def _finish():
        l = l_ref[:, :1]
        m = m_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            m + jnp.log(jnp.maximum(l, 1e-30)), lse_ref.shape[1:])


def _pad_seq(a, s_pad):
    s = a.shape[1]
    if s == s_pad:
        return a
    return jnp.pad(a, ((0, 0), (0, s_pad - s), (0, 0)))


def _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                    interpret=False):
    # the kernels run matmuls on the operands' own dtype (bf16-native
    # MXU, f32 accumulation) — promote mixed inputs to one dtype here
    # so a bf16 q with an f32 KV cache doesn't die inside the kernel
    # (and silently fall back to dense through callers' try/except)
    ct = jnp.promote_types(jnp.promote_types(q.dtype, k.dtype), v.dtype)
    q, k, v = q.astype(ct), k.astype(ct), v.astype(ct)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, sq_pad = _block_and_pad(sq, block_q)
    bk, sk_pad = _block_and_pad(sk, block_k)
    kv_len = sk if sk_pad != sk else None
    num_kv = sk_pad // bk
    qr = _pad_seq(q.reshape(b * h, sq, d), sq_pad)
    kr = _pad_seq(k.reshape(b * h, sk, d), sk_pad)
    vr = _pad_seq(v.reshape(b * h, sk, d), sk_pad)
    kernel = functools.partial(
        _fa_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=bq, block_k=bk, num_kv=num_kv, kv_len=kv_len)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b * h, sq_pad, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, sq_pad, _STAT_LANES),
                                        jnp.float32)),
        grid=(b * h, sq_pad // bq, num_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, _STAT_LANES),
                         lambda bh, qi, ki: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return (out[:, :sq].reshape(b, h, sq, d),
            lse[:, :sq, 0].reshape(b, h, sq))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_acc, *,
                      sm_scale, causal, block_q, block_k, num_kv,
                      kv_len=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = (qi + 1) * block_q > ki * block_k if causal else True
    if kv_len is not None:
        kv_run = ki * block_k < kv_len
        run = kv_run if run is True else jnp.logical_and(run, kv_run)

    @pl.when(run)
    def _step():
        # bf16 matmul operands, f32 accumulation/statistics (see fwd)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                           # [BQ, 1]
        delta = delta_ref[0][:, :1]                       # [BQ, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        s = _mask_scores(s, qi, ki, block_q, block_k, causal, kv_len)
        p = jnp.exp(s - lse)                              # [BQ, BK]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_kv - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *,
                       sm_scale, causal, block_q, block_k, num_q,
                       kv_len=None):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (qi + 1) * block_q > ki * block_k if causal else True
    if kv_len is not None:
        kv_run = ki * block_k < kv_len
        run = kv_run if run is True else jnp.logical_and(run, kv_run)

    @pl.when(run)
    def _step():
        # bf16 matmul operands, f32 accumulation/statistics (see fwd)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                           # [BQ, 1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        s = _mask_scores(s, qi, ki, block_q, block_k, causal, kv_len)
        p = jnp.exp(s - lse)                              # [BQ, BK]
        pb = p.astype(do.dtype)
        # dv_j += p^T @ do
        dv_acc[...] += jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        # dk_j += ds^T @ q
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_impl(q, k, v, o, lse, do, causal, sm_scale,
                    block_q, block_k, interpret=False):
    ct = jnp.promote_types(jnp.promote_types(q.dtype, k.dtype),
                           jnp.promote_types(v.dtype, do.dtype))
    q, k, v, do = (q.astype(ct), k.astype(ct), v.astype(ct),
                   do.astype(ct))
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, sq_pad = _block_and_pad(sq, block_q)
    bk, sk_pad = _block_and_pad(sk, block_k)
    kv_len = sk if sk_pad != sk else None
    num_q = sq_pad // bq
    num_kv = sk_pad // bk
    qr = _pad_seq(q.reshape(b * h, sq, d), sq_pad)
    kr = _pad_seq(k.reshape(b * h, sk, d), sk_pad)
    vr = _pad_seq(v.reshape(b * h, sk, d), sk_pad)
    dor = _pad_seq(do.reshape(b * h, sq, d), sq_pad)
    # per-row stats ride a small trailing lane dim (TPU block tiling).
    # Padded q rows carry lse=0 with do=0, so every gradient
    # contribution they could make is exactly 0 (see _block_and_pad)
    lser = jnp.broadcast_to(
        _pad_seq(lse.reshape(b * h, sq)[:, :, None], sq_pad),
        (b * h, sq_pad, _STAT_LANES))
    # delta_i = rowsum(do_i * o_i) — cheap fused elementwise + reduce
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(b * h, sq)
    delta = jnp.broadcast_to(
        _pad_seq(delta[:, :, None], sq_pad),
        (b * h, sq_pad, _STAT_LANES))

    q_spec = pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0),
                          memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, bq, _STAT_LANES),
                            lambda bh, qi, ki: (bh, qi, 0),
                            memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=bq, block_k=bk,
                          num_kv=num_kv, kv_len=kv_len),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_pad, d), q.dtype),
        grid=(b * h, num_q, num_kv),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)

    # dkv grid: (bh, kv_blocks, q_blocks) — q streams innermost
    q_spec2 = pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0),
                           memory_space=pltpu.VMEM)
    k_spec2 = pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0),
                           memory_space=pltpu.VMEM)
    row_spec2 = pl.BlockSpec((1, bq, _STAT_LANES),
                             lambda bh, ki, qi: (bh, qi, 0),
                             memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=bq, block_k=bk,
                          num_q=num_q, kv_len=kv_len),
        out_shape=(jax.ShapeDtypeStruct((b * h, sk_pad, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, sk_pad, d), v.dtype)),
        grid=(b * h, num_kv, num_q),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, row_spec2,
                  row_spec2],
        out_specs=(k_spec2, k_spec2),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)

    return (dq[:, :sq].reshape(b, h, sq, d),
            dk[:, :sk].reshape(b, h, sk, d),
            dv[:, :sk].reshape(b, h, sk, d))


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

def _attn_ref(q, k, v, causal, sm_scale):
    """Dense reference (testing / tiny shapes only — O(S^2) HBM)."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return p, jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, sm_scale=1.0,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False):
    out, _ = _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                             interpret)
    return out


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                               interpret)
    return out, (q, k, v, out, lse)


def _bwd(causal, sm_scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, o, lse, do, causal, sm_scale,
                                 block_q, block_k, interpret)
    # custom_vjp contract: cotangents match the PRIMAL dtypes even
    # when mixed inputs were promoted inside the impl
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


flash_attention.defvjp(_fwd, _bwd)


def flash_attention_on_mesh(q, k, v, causal, sm_scale):
    """flash_attention for call sites that may be traced under a
    device mesh. Mosaic kernels cannot be partitioned by GSPMD ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call
    in a shard_map"), so under a live multi-device mesh the call is a
    shard_map island: q/k/v [B, H, S, D] split over 'dp' on batch and
    'mp' on heads (each where the axis exists and divides), sequence
    and head_dim whole on every shard — attention needs no
    communication across batch or heads."""
    from jax.sharding import PartitionSpec as P

    from ...distributed import mesh as mesh_mod
    from .pallas import _partitioned
    from .ring_attention import _pick_axis

    if not _partitioned():
        return flash_attention(q, k, v, causal, sm_scale)
    mesh = mesh_mod.get_mesh()
    spec = P(_pick_axis(mesh, "dp", q.shape[0]),
             _pick_axis(mesh, "mp", q.shape[1]), None, None)
    return mesh_mod.shard_map_compat(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal, sm_scale),
        mesh, (spec, spec, spec), spec)(q, k, v)
