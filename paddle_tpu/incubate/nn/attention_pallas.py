"""Pallas TPU flash-attention kernels (forward AND backward).

Replaces the reference's fused CUDA attention
(paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h) with
TPU-native tiled kernels: online softmax over blocks of keys, so the
[S, S] score matrix never exists; QK^T and PV ride the MXU with fp32
accumulation.

One algorithm, three kernels, two ways to bring the operands in:

- a grid step holds a BLOCK of queries and a BLOCK of keys in VMEM and
  meets them sub-block by sub-block in straight-line code: forward and
  dq take `block_q` query rows at a time against every key of the
  block those rows can see, in ONE score tile; dkv takes `block_k` keys
  against every query that can see them. Where the causal diagonal
  crosses the pair of blocks, a sub-block's tile stops at the diagonal
  (dkv's starts there) — nothing above it is loaded, multiplied or
  exponentiated — and what is left of it is masked (the compare and
  the select hide under the MXU: masking the diagonal part alone read
  the same time to four digits, PERF.md PR 37). Every bound is a
  Python int: there is no loop in the bodies (a rolled `fori_loop`
  over chunks read 1.5-1.8x slower on the same grid).
- RESIDENT: where both sides' bytes and the whole square fit
  (`_block_rows`), a block is the whole padded sequence, a head is one
  grid step and q, k, v are fetched once. STREAMED: longer sequences
  meet in blocks of `_STREAM_ROWS` over a grid (head, held block,
  walked block), the accumulators in VMEM scratch between steps; a
  pair wholly above the diagonal is skipped and not fetched (its index
  map stays on the last block needed). The choice is by shape alone;
  `kernels/flash/{resident,streamed}` count it while a program is
  traced.
- the backward recomputes probabilities from q, k and the saved
  logsumexp (the standard flash backward, O(S) memory); dkv computes
  the TRANSPOSED tiles (keys on sublanes), so all of its matmuls are
  plain `A @ B` / `A @ B^T` and the row statistics, which travel as
  lane-dense rows `[1, S]`, broadcast along sublanes.
- `interpret=True` runs the same kernels through the Pallas interpreter
  so correctness is testable on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import monitor as _cmon

# PR 37's sweep on the chip (benchmarks/attn_bench.py; B=12 H=16 S=1024
# D=64 bf16 causal, device ms a call of forward + dq + dkv; PERF.md
# section 7 has the whole table): the parent's one (1024, 1024) tile a
# head 0.790 + 2.094 = 2.883; this file at (block_q, block_k) =
# (128, 128) 1.913, (256, 256) 1.917, (512, 512) 2.061, (1024, x)
# 2.66. The sub-block is a trade: 256 rows compute 10 of the square's
# 16 tiles (512: 6 of 8, 1024: all of it), 128 rows pay the per-row
# work of a sub-block (its [rows, 1] statistics) twice as often for
# 6 % fewer scores. The forward alone prefers 512 (0.616 against
# 0.684), dq 256 and dkv 128-256; one pair serves all three. The same
# pair is within 1 % of the best of the three at D=128 and at S = 2048,
# 4096, 8192.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256

# a side (K and V; Q and dO) lies whole in VMEM while one buffer of the
# pair is no larger than this; the pipeline holds two
_RESIDENT_BYTES = 2 * 1024 * 1024
# ... and the whole square is unrolled into one body while it holds no
# more scores than this. Where the line fell (same sweep): 2048 x 2048
# whole 3.05 ms a call of the three against 4.05 in 1024-row blocks
# (one fetch a head, no carries through scratch), its three bodies
# compiled in 2.6 s; 4096 x 4096 whole is four times the code and was
# refused by the compiler for VMEM at the 16 MiB it grants by default
_RESIDENT_SCORES = 4 * 1024 * 1024
# rows of a streamed block: the parent's block, which is then one
# unrolled 1024 x 1024 meeting on the diagonal and one below it
_STREAM_ROWS = 1024


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


def _block_rows(sq_pad, bq, sk_pad, bk, d, itemsize):
    """(rows of a query block, rows of a key block) that one grid step
    holds in VMEM: both sequences whole (RESIDENT) where a buffer of
    each side's pair fits `_RESIDENT_BYTES` (a row's lanes pad to 128
    there) and the square fits `_RESIDENT_SCORES`; else (STREAMED) on
    each side the largest multiple of its sub-block up to
    `_STREAM_ROWS` that divides the sequence."""
    row_bytes = 2 * (-(-d // 128) * 128) * itemsize
    if (max(sq_pad, sk_pad) * row_bytes <= _RESIDENT_BYTES
            and sq_pad * sk_pad <= _RESIDENT_SCORES):
        return sq_pad, sk_pad

    def rows(seq_pad, sub):
        n = seq_pad // sub
        return sub * max(m for m in range(1, n + 1)
                         if n % m == 0 and m * sub <= max(_STREAM_ROWS, sub))

    return rows(sq_pad, bq), rows(sk_pad, bk)


def _count_path(resident):
    """Which way a flash call's operands come in, counted while the
    program is traced."""
    _cmon.stat_add("kernels/flash/resident" if resident
                   else "kernels/flash/streamed", 1)


_NEG_INF = -1e30
# A @ B^T: contract the last dim of both
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _mask_scores(s, q0, k0, causal, kv_len, q_axis=0):
    """Causal and/or padded-KV masking of one score tile whose first
    query / key sit at positions q0 / k0; queries run along `q_axis`
    (1 for dkv's transposed tiles). kv_len is the REAL key length;
    positions >= kv_len are padding and score -inf (exp underflows to
    exactly 0 — padded keys contribute nothing, bit-exactly).
    kv_len=None means no padding."""
    if not causal and kv_len is None:
        return s
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    ok = None
    if causal:
        ok = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                           q_axis) >= k_pos
    if kv_len is not None:
        real = k_pos < kv_len
        ok = real if ok is None else jnp.logical_and(ok, real)
    return jnp.where(ok, s, _NEG_INF)


def _keys_seen(q0, block_q, base, block_k, n, causal):
    """How many of `n` chunks of `block_k` keys that start at `base`
    the `block_q` queries at q0 meet: the chunks past it lie wholly
    above the diagonal and are left out of the tile."""
    if not causal:
        return n
    return min(pl.cdiv(max(q0 + block_q - base, 0), block_k), n)


def _first_query_chunk(k0, base, block_q, n, causal):
    """The first of `n` chunks of `block_q` queries that start at
    `base` which the keys from k0 on meet: the chunks before it lie
    wholly above the diagonal and are left out of the tile."""
    if not causal:
        return 0
    return min(max(k0 - base, 0) // block_q, n)


def _meetings(n_q, rows_q, n_k, rows_k, causal, padded):
    """The distinct ways a block of queries meets a block of keys over
    a grid of n_q x n_k blocks, as {(offset, last): (qi, ki)} with one
    pair standing for each: `offset` = first query - first key where
    the causal diagonal crosses the pair, None where every query sees
    every key (pairs wholly above the diagonal are left out: skipped);
    `last` = the key block that holds the padding. A body is traced
    for each, with Python ints for every position."""
    out = {}
    for qi in range(n_q):
        for ki in range(n_k):
            off = qi * rows_q - ki * rows_k
            if causal and off + rows_q <= 0:
                continue
            if not causal or off + 1 >= rows_k:
                off = None
            out.setdefault((off, padded and ki == n_k - 1), (qi, ki))
    return out


def _for_each_meeting(qi, ki, meetings, rows_q, rows_k, n_k, causal, body):
    """`body(first query, first key)` of the meeting that the grid's
    (qi, ki) is, with the positions of the pair that stands for it;
    what holds for every pair is not tested."""
    off = qi * rows_q - ki * rows_k
    flagged = any(last for _, last in meetings)
    for (o, last), (q, k) in meetings.items():
        conds = []
        if o is not None:
            conds.append(off == o)
        elif causal:
            conds.append(off + 1 >= rows_k)
        if flagged:
            conds.append(ki == n_k - 1 if last else ki != n_k - 1)
        conds = [c for c in conds if c is not True]
        run = functools.partial(body, q * rows_q, k * rows_k)
        if not conds:
            run()
        else:
            pl.when(functools.reduce(jnp.logical_and, conds))(run)


def _block_ids(grid):
    """The grid's (held, walked) block indices; a Python 0 along an
    axis of one block, so that a resident kernel tests nothing."""
    return tuple(0 if n == 1 else pl.program_id(axis)
                 for axis, n in zip((1, 2), grid))


def _carry(j, n, scratch, fills, shapes, finish):
    """How a sub-block's accumulators live through an accumulation over
    `n` walked blocks, of which this grid step is the j-th:
    `begin(rows) -> carry` and `end(rows, carry)` around one meeting,
    `close()` after them. `finish(rows, carry)` writes the outputs.
    One walked block (the resident case) keeps the carry in values and
    finishes a sub-block at once; a stream keeps it in `scratch`
    ([rows, 1] statistics ride 128 lanes there) between grid steps and
    finishes the whole held block on the last."""
    if n == 1:
        def begin(rows):
            return tuple(jnp.full(s, f, jnp.float32)
                         for s, f in zip(shapes, fills))
        return begin, finish, lambda: None

    @pl.when(j == 0)
    def _init():
        for ref, f in zip(scratch, fills):
            ref[...] = jnp.full(ref.shape, f, jnp.float32)

    def begin(rows):
        return tuple(ref[rows, :s[1]] for ref, s in zip(scratch, shapes))

    def end(rows, carry):
        for ref, x in zip(scratch, carry):
            ref[rows, :] = jnp.broadcast_to(x, (x.shape[0], ref.shape[1]))

    def close():
        @pl.when(j == n - 1)
        def _finish():
            finish(slice(None), begin(slice(None)))

    return begin, end, close


def _carry_scratch(n, rows, *widths):
    """VMEM scratch for `_carry`: none for one walked block."""
    if n == 1:
        return []
    return [pltpu.VMEM((rows, 128 if w == 1 else w), jnp.float32)
            for w in widths]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                   sm_scale, causal, block_q, block_k, grid, meetings,
                   kv_len=None):
    qi, kj = _block_ids(grid)
    rows_q, rows_k, d = q_ref.shape[1], k_ref.shape[1], q_ref.shape[2]

    def finish(rows, carry):
        m, l, acc = carry
        l = jnp.maximum(l, 1e-30)
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        # the logsumexp leaves as ONE lane-dense row
        lse_ref[0, :, rows] = (m + jnp.log(l)).reshape(1, -1)

    begin, end, close = _carry(
        kj, grid[1], scratch, (_NEG_INF, 0.0, 0.0),
        ((block_q, 1), (block_q, 1), (block_q, d)), finish)

    def meet(q_first, k_first):
        for j in range(rows_q // block_q):
            rows = pl.ds(j * block_q, block_q)
            q0 = q_first + j * block_q
            seen = _keys_seen(q0, block_q, k_first, block_k,
                              rows_k // block_k, causal)
            m_prev, l_prev, acc = carry = begin(rows)
            if seen:
                # keep matmul OPERANDS in the input dtype (bf16): the
                # MXU is bf16-native with f32 accumulation — casting
                # q/k/v up to f32 before the dots ran the matmuls on
                # the slow f32 path (r5). Softmax statistics stay f32
                # (preferred_element_type).
                q = q_ref[0, rows, :]                     # [BQ, D]
                keys = pl.ds(0, seen * block_k)
                k = k_ref[0, keys, :]                     # [seen * BK, D]
                v = v_ref[0, keys, :]
                s = jax.lax.dot_general(
                    q, k, _NT, preferred_element_type=jnp.float32)
                s = _mask_scores(s * sm_scale, q0, k_first, causal, kv_len)
                m_new = jnp.max(s, axis=1, keepdims=True)
                if grid[1] > 1:
                    m_new = jnp.maximum(m_prev, m_new)
                p = jnp.exp(s - m_new)
                l_new = jnp.sum(p, axis=1, keepdims=True)
                acc_new = jax.lax.dot_general(
                    p.astype(v.dtype), v, _NN,
                    preferred_element_type=jnp.float32)
                if grid[1] > 1:
                    # the online softmax's correction of what earlier
                    # key blocks left; one key block leaves nothing
                    alpha = jnp.exp(m_prev - m_new)
                    l_new = l_prev * alpha + l_new
                    acc_new = acc * alpha + acc_new
                carry = m_new, l_new, acc_new
            end(rows, carry)

    _for_each_meeting(qi, kj, meetings, rows_q, rows_k, grid[1], causal,
                      meet)
    close()


def _pad_seq(a, s_pad):
    s = a.shape[1]
    if s == s_pad:
        return a
    return jnp.pad(a, ((0, 0), (0, s_pad - s), (0, 0)))


# 96 MiB of a v5e's 128 MiB of VMEM, as pallas/grouped_matmul.py takes
# (this installation's one chip; a chip with less refuses the kernel
# when it compiles, it does not miscompute). Need: at 256-wide heads a
# resident call (2 MiB a buffer of each pair, two buffers, the
# outputs, a sub-block's [256, 2048] f32 tiles) takes more than the
# 16 MiB the compiler grants by default. Beyond the need: XLA sets the
# whole limit aside around the call, and what it may keep in VMEM
# across the kernels decides its schedule of the train step: the
# step's temporaries at B=12 (chip_scratch/pr37_compile_here.py, the
# compiler's own figure) against the parent's one-tile kernels: +90 MB
# at the default, +38 at 32 MiB, +45 at 64, -153 at 96, -152 at 112
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=96 * 1024 * 1024)


def _plan(sq_pad, bq, sk_pad, bk, d, itemsize, causal, kv_len):
    """(rows of a query block, rows of a key block, (query blocks, key
    blocks), meetings) of one flash call."""
    rows_q, rows_k = _block_rows(sq_pad, bq, sk_pad, bk, d, itemsize)
    n_q, n_k = sq_pad // rows_q, sk_pad // rows_k
    return rows_q, rows_k, (n_q, n_k), _meetings(
        n_q, rows_q, n_k, rows_k, causal, kv_len is not None)


def _specs(rows_q, rows_k, d, n_q, causal, keys_walk):
    """BlockSpecs (a query-side [rows, D] operand, a key-side one, the
    query-side row statistics: one lane-dense row) of a grid (head,
    held block, walked block); forward and dq hold queries and walk
    keys, dkv the other way round. A causal stream's skipped steps
    name the nearest block they need, so nothing is fetched for them;
    keys past the last query (sk > sq) need none and name the last."""
    def ids(bh, held, walked):
        qi, ki = (held, walked) if keys_walk else (walked, held)
        if causal and keys_walk:
            ki = jnp.minimum(ki, (qi * rows_q + rows_q - 1) // rows_k)
        elif causal:
            qi = jnp.minimum(jnp.maximum(qi, (ki * rows_k) // rows_q),
                             n_q - 1)
        return bh, qi, ki

    def q_map(*g):
        bh, qi, _ = ids(*g)
        return bh, qi, 0

    def k_map(*g):
        bh, _, ki = ids(*g)
        return bh, ki, 0

    def row_map(*g):
        bh, qi, _ = ids(*g)
        return bh, 0, qi

    vmem = pltpu.VMEM
    return (pl.BlockSpec((1, rows_q, d), q_map, memory_space=vmem),
            pl.BlockSpec((1, rows_k, d), k_map, memory_space=vmem),
            pl.BlockSpec((1, 1, rows_q), row_map, memory_space=vmem))


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
    rows_q, rows_k, grid, meetings = _plan(
        sq_pad, bq, sk_pad, bk, d, ct.itemsize, causal, kv_len)
    _count_path(grid == (1, 1))
    qr = _pad_seq(q.reshape(b * h, sq, d), sq_pad)
    kr = _pad_seq(k.reshape(b * h, sk, d), sk_pad)
    vr = _pad_seq(v.reshape(b * h, sk, d), sk_pad)
    q_spec, k_spec, row_spec = _specs(rows_q, rows_k, d, grid[0], causal, True)
    out, lse = pl.pallas_call(
        functools.partial(
            _fa_fwd_kernel, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, grid=grid, meetings=meetings,
            kv_len=kv_len),
        out_shape=(jax.ShapeDtypeStruct((b * h, sq_pad, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, sq_pad), jnp.float32)),
        grid=(b * h, *grid),
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=(q_spec, row_spec),
        scratch_shapes=_carry_scratch(grid[1], rows_q, 1, 1, d),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(qr, kr, vr)
    return (out[:, :sq].reshape(b, h, sq, d),
            lse[:, 0, :sq].reshape(b, h, sq))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, *scratch,
                      sm_scale, causal, block_q, block_k, grid, meetings,
                      kv_len=None):
    qi, kj = _block_ids(grid)
    rows_q, rows_k, d = q_ref.shape[1], k_ref.shape[1], q_ref.shape[2]

    def finish(rows, carry):
        (dq,) = carry
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)

    begin, end, close = _carry(kj, grid[1], scratch, (0.0,),
                               ((block_q, d),), finish)

    def meet(q_first, k_first):
        for j in range(rows_q // block_q):
            rows = pl.ds(j * block_q, block_q)
            q0 = q_first + j * block_q
            seen = _keys_seen(q0, block_q, k_first, block_k,
                              rows_k // block_k, causal)
            (dq,) = begin(rows)
            if seen:
                # bf16 matmul operands, f32 accumulation/statistics
                # (see fwd)
                q = q_ref[0, rows, :]
                do = do_ref[0, rows, :]
                lse = lse_ref[0, :, rows].reshape(block_q, 1)
                delta = delta_ref[0, :, rows].reshape(block_q, 1)
                keys = pl.ds(0, seen * block_k)
                k = k_ref[0, keys, :]
                v = v_ref[0, keys, :]
                s = jax.lax.dot_general(
                    q, k, _NT, preferred_element_type=jnp.float32)
                s = _mask_scores(s * sm_scale, q0, k_first, causal, kv_len)
                p = jnp.exp(s - lse)                      # [BQ, seen * BK]
                dp = jax.lax.dot_general(
                    do, v, _NT, preferred_element_type=jnp.float32)
                ds = p * (dp - delta) * sm_scale
                dq = dq + jax.lax.dot_general(
                    ds.astype(k.dtype), k, _NN,
                    preferred_element_type=jnp.float32)
            end(rows, (dq,))

    _for_each_meeting(qi, kj, meetings, rows_q, rows_k, grid[1], causal,
                      meet)
    close()


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, *scratch,
                       sm_scale, causal, block_q, block_k, grid, meetings,
                       kv_len=None):
    ki, qj = _block_ids(grid[::-1])
    rows_q, rows_k, d = q_ref.shape[1], k_ref.shape[1], q_ref.shape[2]

    def finish(rows, carry):
        dk, dv = carry
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)

    begin, end, close = _carry(qj, grid[0], scratch, (0.0, 0.0),
                               ((block_k, d), (block_k, d)), finish)

    def meet(q_first, k_first):
        n = rows_q // block_q
        for j in range(rows_k // block_k):
            rows = pl.ds(j * block_k, block_k)
            k0 = k_first + j * block_k
            start = _first_query_chunk(k0, q_first, block_q, n, causal)
            dk, dv = begin(rows)
            if start < n:
                # bf16 matmul operands, f32 accumulation/statistics
                # (see fwd). The tile is TRANSPOSED, [BK, queries]:
                # keys on sublanes, so the row statistics broadcast
                # along sublanes and no matmul needs a transposed left
                # operand
                k = k_ref[0, rows, :]                     # [BK, D]
                v = v_ref[0, rows, :]
                seen = pl.ds(start * block_q, (n - start) * block_q)
                q = q_ref[0, seen, :]                     # [queries, D]
                do = do_ref[0, seen, :]
                lse = lse_ref[0, :, seen]                 # [1, queries]
                delta = delta_ref[0, :, seen]
                st = jax.lax.dot_general(
                    k, q, _NT, preferred_element_type=jnp.float32)
                st = _mask_scores(st * sm_scale, q_first + start * block_q,
                                  k0, causal, kv_len, q_axis=1)
                pt = jnp.exp(st - lse)                    # [BK, queries]
                # dv_j += p^T @ do
                dv = dv + jax.lax.dot_general(
                    pt.astype(do.dtype), do, _NN,
                    preferred_element_type=jnp.float32)
                dpt = jax.lax.dot_general(
                    v, do, _NT, preferred_element_type=jnp.float32)
                dst = pt * (dpt - delta) * sm_scale
                # dk_j += ds^T @ q
                dk = dk + jax.lax.dot_general(
                    dst.astype(q.dtype), q, _NN,
                    preferred_element_type=jnp.float32)
            end(rows, (dk, dv))

    _for_each_meeting(qj, ki, meetings, rows_q, rows_k, grid[1], causal,
                      meet)
    close()


def _flash_dq(qr, kr, vr, dor, lse, delta, causal, sm_scale, bq, bk,
              kv_len, interpret):
    """dq of [BH, S_pad, D] operands; lse / delta [BH, Sq_pad] f32."""
    bh, sq_pad, d = qr.shape
    rows_q, rows_k, grid, meetings = _plan(
        sq_pad, bq, kr.shape[1], bk, d, qr.dtype.itemsize, causal, kv_len)
    q_spec, k_spec, row_spec = _specs(rows_q, rows_k, d, grid[0], causal, True)
    rows = [x.reshape(bh, 1, sq_pad) for x in (lse, delta)]
    return pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=bq, block_k=bk,
                          grid=grid, meetings=meetings, kv_len=kv_len),
        out_shape=jax.ShapeDtypeStruct((bh, sq_pad, d), qr.dtype),
        grid=(bh, *grid),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=_carry_scratch(grid[1], rows_q, d),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(qr, kr, vr, dor, *rows)


def _flash_dkv(qr, kr, vr, dor, lse, delta, causal, sm_scale, bq, bk,
               kv_len, interpret):
    """(dk, dv) of [BH, S_pad, D] operands; lse / delta [BH, Sq_pad]
    f32. Grid (head, key block, query block): the queries walk."""
    bh, sq_pad, d = qr.shape
    sk_pad = kr.shape[1]
    rows_q, rows_k, grid, meetings = _plan(
        sq_pad, bq, sk_pad, bk, d, qr.dtype.itemsize, causal, kv_len)
    q_spec, k_spec, row_spec = _specs(rows_q, rows_k, d, grid[0], causal,
                                      False)
    rows = [x.reshape(bh, 1, sq_pad) for x in (lse, delta)]
    return pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=bq, block_k=bk,
                          grid=grid, meetings=meetings, kv_len=kv_len),
        out_shape=(jax.ShapeDtypeStruct((bh, sk_pad, d), kr.dtype),
                   jax.ShapeDtypeStruct((bh, sk_pad, d), vr.dtype)),
        grid=(bh, *grid[::-1]),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=(k_spec, k_spec),
        scratch_shapes=_carry_scratch(grid[0], rows_k, d, d),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(qr, kr, vr, dor, *rows)


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
    _count_path(_block_rows(sq_pad, bq, sk_pad, bk, d, ct.itemsize)
                == (sq_pad, sk_pad))
    qr = _pad_seq(q.reshape(b * h, sq, d), sq_pad)
    kr = _pad_seq(k.reshape(b * h, sk, d), sk_pad)
    vr = _pad_seq(v.reshape(b * h, sk, d), sk_pad)
    dor = _pad_seq(do.reshape(b * h, sq, d), sq_pad)
    # delta_i = rowsum(do_i * o_i) — cheap fused elementwise + reduce.
    # Padded q rows carry lse=0 with do=0, so every gradient
    # contribution they could make is exactly 0 (see _block_and_pad)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lser, delta = (jnp.pad(x.reshape(b * h, sq), ((0, 0), (0, sq_pad - sq)))
                   if sq_pad != sq else x.reshape(b * h, sq)
                   for x in (lse, delta))
    args = (qr, kr, vr, dor, lser, delta, causal, sm_scale, bq, bk,
            kv_len, interpret)
    dq = _flash_dq(*args)
    dk, dv = _flash_dkv(*args)
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
