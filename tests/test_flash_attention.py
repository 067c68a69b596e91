"""Flash-attention Pallas kernel correctness (interpret mode on CPU).

Parity target: fused attention numerics
(/root/reference/paddle/fluid/operators/fused/fmha_ref.h). The kernels
are validated against the dense softmax-attention reference for both
forward and all three gradients, causal and non-causal.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.core.monitor import stat_get
from paddle_tpu.incubate.nn import attention_pallas as ap
from paddle_tpu.incubate.nn.attention_pallas import (
    _attn_ref, flash_attention)

ON_TPU = any(d.platform == "tpu" for d in jax.devices())


def _rand_qkv(b=1, h=2, s=256, d=64, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32) * 0.5
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(causal):
    q, k, v = _rand_qkv()
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = flash_attention(q, k, v, causal, scale, 128, 128, True)
    _, ref = _attn_ref(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    q, k, v = _rand_qkv(s=256)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, scale, 128, 128,
                                       True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_attn_ref(q, k, v, causal, scale)[1] ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_uneven_blocks():
    # seq 384 with 128-blocks: 3 kv blocks, partial diagonal coverage
    q, k, v = _rand_qkv(s=384, d=64, seed=3)
    scale = 0.125
    out = flash_attention(q, k, v, True, scale, 128, 128, True)
    _, ref = _attn_ref(q, k, v, True, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_block_q_ne_block_k():
    q, k, v = _rand_qkv(s=512, seed=4)
    scale = 0.125
    out = flash_attention(q, k, v, True, scale, 256, 128, True)
    _, ref = _attn_ref(q, k, v, True, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _flash_paths():
    return (stat_get("kernels/flash/resident"),
            stat_get("kernels/flash/streamed"))


def _loss_8k(q, k, v):
    return jnp.sum(flash_attention(q, k, v, True, 0.125).astype(
        jnp.float32))


def test_flash_8k_takes_the_streamed_path():
    """8192 keys of 64 bf16 values do not fit the residency budget:
    forward and backward stream 1024-row major blocks, chosen by shape
    alone and counted while the program is traced (nothing runs)."""
    sd = jax.ShapeDtypeStruct((1, 4, 8192, 64), jnp.bfloat16)
    resident, streamed = _flash_paths()
    jax.eval_shape(jax.value_and_grad(_loss_8k, argnums=(0, 1, 2)),
                   sd, sd, sd)
    assert _flash_paths() == (resident, streamed + 2)


@pytest.mark.skipif(not ON_TPU, reason="long-seq memory test needs TPU")
def test_flash_long_sequence_8k():
    """seq=8192: dense attention would materialize a 8k x 8k f32 score
    matrix per head (256 MB x heads); flash streams KV tiles and must
    run fwd+bwd within VMEM/HBM budget."""
    q, k, v = _rand_qkv(b=1, h=4, s=8192, d=64)
    q = q.astype(jnp.bfloat16)
    k = k.astype(jnp.bfloat16)
    v = v.astype(jnp.bfloat16)
    resident, streamed = _flash_paths()
    loss, grads = jax.jit(jax.value_and_grad(
        _loss_8k, argnums=(0, 1, 2)))(q, k, v)
    assert _flash_paths() == (resident, streamed + 2)
    assert np.isfinite(float(loss))
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# ISSUE 37: the walked operand whole in VMEM (resident) or in major
# blocks (streamed), the walk to the diagonal
# ---------------------------------------------------------------------------

def _dense_top_left(q, k, v, causal, scale):
    """Dense attention with the kernels' causal alignment: query i
    sees keys <= i, also where sq != sk (`_attn_ref` aligns the LAST
    query with the last key there)."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        sq, sk = logits.shape[-2:]
        logits = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), logits,
                           -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v)


def _check_fwd_and_grads(sq, sk, d, causal, bq, bk, seed=11):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(1, 2, sq, d), jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(1, 2, sk, d), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(1, 2, sk, d), jnp.float32) * 0.5
    w = jnp.asarray(rng.randn(1, 2, sq, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)      # a power of two at 64, not at 128
    ref = _dense_top_left if sq != sk else (
        lambda *a: _attn_ref(*a)[1])

    def f_flash(q, k, v):
        o = flash_attention(q, k, v, causal, scale, bq, bk, True)
        return jnp.sum(o * w), o

    def f_ref(q, k, v):
        o = ref(q, k, v, causal, scale)
        return jnp.sum(o * w), o

    (_, o1), g1 = jax.value_and_grad(f_flash, (0, 1, 2), has_aux=True)(
        q, k, v)
    (_, o2), g2 = jax.value_and_grad(f_ref, (0, 1, 2), has_aux=True)(
        q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)
    for gf, gr, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")
    return o1, g1


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("sq,sk", [(512, 512), (129, 129), (384, 256),
                                   (256, 384)],
                         ids=["divides", "padded129", "cross",
                              "cross_sk_gt_sq"])
@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 128), (128, 256)],
                         ids=["bq_eq_bk", "bq_gt_bk", "bq_lt_bk"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_resident_matches_dense(causal, bq, bk, sq, sk, d):
    """K/V (Q/dO for dkv) whole in VMEM, the walk inside the body:
    forward and all three gradients against dense attention."""
    resident, streamed = _flash_paths()
    _check_fwd_and_grads(sq, sk, d, causal, bq, bk)
    assert _flash_paths() == (resident + 2, streamed)


def test_flash_cell_shape_default_blocks():
    """The train cells' own shape with few heads (1 x 2 x 1024 x 64)
    at the default blocks: resident, forward and backward."""
    resident, streamed = _flash_paths()
    _check_fwd_and_grads(1024, 1024, 64, True, ap.DEFAULT_BLOCK_Q,
                         ap.DEFAULT_BLOCK_K)
    assert _flash_paths() == (resident + 2, streamed)


@pytest.mark.parametrize("sq,sk,rows", [(512, 512, 256), (129, 129, 128),
                                        (384, 256, 128), (256, 384, 128)],
                         ids=["divides", "padded129", "cross",
                              "cross_sk_gt_sq"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_streamed_matches_dense(monkeypatch, causal, sq, sk, rows):
    """The same kernels with the walked operand in major blocks over
    the innermost grid axis and the accumulators in scratch between
    steps: the budget shrunk so that small shapes stream (on the chip
    the shape decides: test_flash_8k_takes_the_streamed_path)."""
    monkeypatch.setattr(ap, "_RESIDENT_BYTES", 0)
    monkeypatch.setattr(ap, "_STREAM_ROWS", rows)
    resident, streamed = _flash_paths()
    _check_fwd_and_grads(sq, sk, 64, causal, 128, 128)
    assert _flash_paths() == (resident, streamed + 2)


@pytest.mark.parametrize("sq,sk,d,itemsize,want", [
    (1024, 1024, 64, 2, (1024, 1024)),   # the train cells: whole
    (2048, 2048, 128, 2, (2048, 2048)),  # the largest square unrolled
    (2048, 2048, 256, 2, (2048, 2048)),  # 2 MiB a buffer: the last whole
    (2048, 2048, 256, 4, (1024, 1024)),  # f32 rows weigh twice: streamed
    (4096, 4096, 64, 2, (1024, 1024)),   # fits VMEM, too long to unroll
    (8192, 8192, 64, 2, (1024, 1024)),
    (8192, 256, 64, 2, (1024, 256)),     # a short side is one block
    (1152, 1152, 64, 2, (1152, 1152)),
    (3456, 3456, 64, 2, (384, 384)),     # 27 x 128: whole sub-blocks
])
def test_block_rows_follow_the_operands_bytes(sq, sk, d, itemsize, want):
    assert ap._block_rows(sq, 256 if sq % 256 == 0 else 128, sk,
                          256 if sk % 256 == 0 else 128, d, itemsize) == want


@pytest.mark.parametrize("causal,padded,want", [
    (False, False, {(None, False): (0, 0)}),
    (False, True, {(None, False): (0, 0), (None, True): (0, 3)}),
    # below the diagonal, on it; above it: no body, no fetch
    (True, False, {(0, False): (0, 0), (None, False): (1, 0)}),
    (True, True, {(0, False): (0, 0), (None, False): (1, 0),
                  (0, True): (3, 3)}),
])
def test_meetings_of_a_streamed_square(causal, padded, want):
    """4 x 4 blocks of 1024: the bodies a streamed kernel holds."""
    assert ap._meetings(4, 1024, 4, 1024, causal, padded) == want


# ---------------------------------------------------------------------------
# divisor-free sequence lengths: padded, masked, bit-exact on the
# unpadded region (ISSUE 8 satellite — _pick_block used to hard-raise)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [7, 129])
def test_flash_padded_sequence_matches_dense(causal, s):
    """Lengths with no power-of-two block divisor pad up inside the
    wrapper; padded KV positions are masked to exactly zero weight and
    padded q rows sliced off."""
    q, k, v = _rand_qkv(s=s, d=16, seed=7)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = flash_attention(q, k, v, causal, scale, 1024, 1024, True)
    _, ref = _attn_ref(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_padded_sequence_grads():
    q, k, v = _rand_qkv(s=129, d=16, seed=8)
    scale = 0.25

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, scale, 1024, 1024,
                                       True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_attn_ref(q, k, v, True, scale)[1] ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_padded_cross_attention():
    # sq != sk, neither divisible: both sides pad independently
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(1, 2, 129, 16), jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(1, 2, 72, 16), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(1, 2, 72, 16), jnp.float32) * 0.5
    out = flash_attention(q, k, v, False, 0.25, 1024, 1024, True)
    _, ref = _attn_ref(q, k, v, False, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_block_and_pad_prefers_divisors():
    from paddle_tpu.incubate.nn.attention_pallas import _block_and_pad

    assert _block_and_pad(1024, 1024) == (1024, 1024)  # exact
    assert _block_and_pad(384, 1024) == (128, 384)     # divisor path
    assert _block_and_pad(129, 1024) == (128, 256)     # padded
    assert _block_and_pad(7, 1024) == (8, 8)           # tiny


def test_flash_attention_on_mesh_is_a_shard_map_island(monkeypatch):
    """Under a live multi-device mesh the flash kernel runs inside a
    shard_map island over (dp on batch, mp on heads) — GSPMD cannot
    partition a Mosaic kernel — and matches dense attention. The
    kernel runs through the interpreter here; on the chip
    chip_smoke.py's multichip phase runs it compiled."""
    import functools

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import build_mesh, set_mesh
    from paddle_tpu.incubate.nn import attention_pallas as ap

    monkeypatch.setattr(
        ap, "flash_attention",
        functools.partial(ap.flash_attention, interpret=True))
    mesh = build_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
    set_mesh(mesh)
    try:
        rng = np.random.RandomState(0)
        sh = NamedSharding(mesh, P("dp", "mp"))
        q, k, v = (jax.device_put(
            jnp.asarray(rng.randn(4, 4, 128, 64), jnp.float32), sh)
            for _ in range(3))
        out = jax.jit(lambda q, k, v: ap.flash_attention_on_mesh(
            q, k, v, True, 0.125))(q, k, v)
        assert out.sharding.is_equivalent_to(sh, 4)
        ref = ap._attn_ref(q, k, v, True, 0.125)[1]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
    finally:
        set_mesh(None)
