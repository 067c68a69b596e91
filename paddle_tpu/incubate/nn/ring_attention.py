"""Ring attention — sequence/context-parallel attention over the 'sp'
mesh axis.

Capability target: SURVEY §5 requires long-context SP/CP as a
first-class axis (the reference snapshot predates it — its ceiling is
fused/sparse attention, `paddle/fluid/operators/fused/fmha_ref.h`).
Extension-point pattern: `fleet/base/topology.py:117` (the 'sep' axis
in our HybridCommunicateGroup).

TPU-native design (Ring Attention / "How to Scale Your Model" recipe):
queries stay put, K/V blocks rotate around the sp ring via
`lax.ppermute` (XLA collective-permute over ICI neighbors — no
all-gather, so per-chip memory stays O(S/sp)). Each of the sp steps
combines the local partial attention with flash-style online-softmax
accumulation (running max m, denominator l, accumulator acc), so the
result is EXACT attention over the full sequence. XLA overlaps each
step's ppermute with the next step's matmuls (the scan body issues the
permute before the compute consumes the previous block).

Use `ring_attention_shard` inside an existing shard_map; use
`ring_attention` on global arrays (it builds the shard_map island —
also valid inside jit, composing with GSPMD-partitioned surroundings).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ...distributed import mesh as mesh_mod

__all__ = ["ring_attention", "ring_attention_shard",
           "ulysses_attention"]


def _chunk_attn_partial(q, k_blk, v_blk, q_off, k_off, causal, sm_scale):
    """Partial (unnormalized) attention of local q against one KV block
    at global offset k_off. Returns (scores_max, exp_scores_sum, pv)
    per flash-attention bookkeeping. Shapes: q [b,h,sq,d],
    k_blk/v_blk [b,h,sk,d]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        sq, sk = q.shape[2], k_blk.shape[2]
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = k_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(q_pos >= k_pos, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)                # [b,h,sq,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    # bf16 matmul operands, f32 accumulation — same MXU policy as the
    # flash kernels (r5); statistics stay f32
    pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
                    preferred_element_type=jnp.float32)
    return m, l, pv


def ring_attention_shard(q, k, v, axis_name="sp", causal=True,
                         sm_scale=None):
    """Exact attention over the full (sp-sharded) sequence; call inside
    shard_map. q/k/v: per-shard [b, h, s_local, d]."""
    # psum of a Python literal over a named axis folds to the static
    # ring size at trace time
    nsteps = int(lax.psum(1, axis_name))
    my = lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    qf = q  # bf16-native MXU: operands stay in input dtype (r5)
    q_off = my * s_local
    perm = [(j, (j + 1) % nsteps) for j in range(nsteps)]

    def combine(carry, i, k_blk, v_blk):
        acc, m, l = carry
        # this block originated at rank (my - i) mod sp
        k_off = ((my - i) % nsteps) * s_local
        m_cur, l_cur, pv = _chunk_attn_partial(
            qf, k_blk, v_blk, q_off, k_off, causal, sm_scale)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_cur - m_new)
        return (acc * alpha + pv * beta, m_new, l * alpha + l_cur * beta)

    def step(carry, i):
        acc, m, l, k_blk, v_blk = carry
        acc, m, l = combine((acc, m, l), i, k_blk, v_blk)
        # rotate KV to the next neighbor (ICI ring); the permute's input
        # doesn't depend on this step's matmuls, so XLA overlaps them
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (acc, m, l, k_blk, v_blk), None

    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    m0 = jnp.full((b, h, s_local, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, s_local, 1), jnp.float32)
    carry = (acc0, m0, l0, k, v)
    if nsteps > 1:
        # scan the first nsteps-1 blocks (each ends with a rotation)…
        carry, _ = lax.scan(step, carry, jnp.arange(nsteps - 1))
    # …and fold in the final block without a wasted trailing permute
    acc, m, l, k_blk, v_blk = carry
    acc, m, l = combine((acc, m, l), nsteps - 1, k_blk, v_blk)
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def _dense_causal_attention(q, k, v, causal, sm_scale):
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _sp_mesh_or_none(mesh, seq_axis):
    """Resolve the live mesh for sequence parallelism; None means
    'no sp axis > 1 — fall back to exact dense attention'."""
    mesh = mesh or mesh_mod.get_mesh()
    if (mesh is None or seq_axis not in mesh.shape
            or mesh.shape[seq_axis] <= 1):
        return None
    return mesh


def _pick_axis(mesh, a, dim):
    """Use mesh axis `a` for a tensor dim only when it exists, is >1,
    and divides the dim."""
    return a if (a in mesh.shape and mesh.shape[a] > 1
                 and dim % mesh.shape[a] == 0) else None


def _shard_map(body, mesh, in_specs, out_specs):
    """The shard_map island builder shared with linalg.dist
    (distributed.mesh.shard_map_compat)."""
    from ...distributed.mesh import shard_map_compat

    return shard_map_compat(body, mesh, in_specs, out_specs)


def ulysses_attention(q, k, v, causal=True, sm_scale=None, mesh=None,
                      batch_axis="dp", head_axis="mp", seq_axis="sp"):
    """Ulysses/DeepSpeed-style sequence parallelism (SURVEY §5:
    "Ulysses-style head-sharded alltoall"): inputs arrive sharded over
    the SEQUENCE dim; one all_to_all re-shards them over the HEAD dim
    (each sp-rank then holds h/sp full-sequence heads), attention runs
    LOCALLY and exactly (any kernel — here the dense/flash path), and a
    second all_to_all restores sequence sharding.

    Two all_to_alls of the qkv/out tensors vs ring's sp ppermutes of
    KV — Ulysses wins when heads >> sp and attention is kernel-bound;
    ring wins on memory for extreme sequence lengths. Requires
    num_heads % sp == 0.

    Host-emulation note: earlier XLA:CPU builds could deadlock when
    this cross-module all_to_all overlapped other collectives at large
    head counts (concurrent-thunk rendezvous ordering races). The
    current runtime is clean — tests/test_ring_attention.py pins the
    previously-failing shapes (heads up to 64 inside the hybrid dp×sp
    train step) as active regression tests."""
    mesh = _sp_mesh_or_none(mesh, seq_axis)
    if mesh is None:
        return _dense_causal_attention(q, k, v, causal, sm_scale)
    sp = mesh.shape[seq_axis]
    b, h, s, d = q.shape
    if h % sp or s % sp:
        return _dense_causal_attention(q, k, v, causal, sm_scale)

    bax = _pick_axis(mesh, batch_axis, b)
    # heads may ALSO stay sharded over the tensor-parallel axis: the
    # island's local all_to_all then splits the per-mp-rank head count
    # by sp, which requires h % (mp * sp) == 0; otherwise heads
    # replicate over mp inside the island (correct, just redundant)
    mp_n = mesh.shape.get(head_axis, 1)
    hax = (head_axis if (head_axis in mesh.shape and mp_n > 1
                         and h % (mp_n * sp) == 0) else None)
    in_spec = P(bax, hax, seq_axis, None)   # seq-sharded in/out
    out_spec = in_spec

    def body(qs, ks, vs):
        # [b, h, s/sp, d] per rank -> tiled all_to_all: scatter the
        # HEAD dim, gather the SEQ dim -> [b, h/sp, s, d] full-sequence
        # heads; the inverse swap restores sequence sharding.
        def seq2head(x):
            return lax.all_to_all(x, seq_axis, split_axis=1,
                                  concat_axis=2, tiled=True)

        def head2seq(x):
            return lax.all_to_all(x, seq_axis, split_axis=2,
                                  concat_axis=1, tiled=True)

        qh, kh, vh = seq2head(qs), seq2head(ks), seq2head(vs)
        oh = _dense_causal_attention(qh, kh, vh, causal, sm_scale)
        return head2seq(oh)

    return _shard_map(body, mesh, (in_spec, in_spec, in_spec),
                      out_spec)(q, k, v)


def ring_attention(q, k, v, causal=True, sm_scale=None, mesh=None,
                   batch_axis="dp", head_axis="mp", seq_axis="sp"):
    """Global-array entry: shard_map island over (batch_axis, head_axis,
    seq_axis). q/k/v: [b, h, s, d] global. Valid inside jit — GSPMD
    reshards surroundings to match. Falls back to single-shard exact
    attention when the mesh has no sp axis > 1."""
    mesh = _sp_mesh_or_none(mesh, seq_axis)
    if mesh is None:
        return _dense_causal_attention(q, k, v, causal, sm_scale)
    if q.shape[2] % mesh.shape[seq_axis]:
        return _dense_causal_attention(q, k, v, causal, sm_scale)
    spec = P(_pick_axis(mesh, batch_axis, q.shape[0]),
             _pick_axis(mesh, head_axis, q.shape[1]),
             seq_axis, None)
    body = functools.partial(ring_attention_shard, axis_name=seq_axis,
                             causal=causal, sm_scale=sm_scale)
    return _shard_map(body, mesh, (spec, spec, spec), spec)(q, k, v)
