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


@pytest.mark.skipif(not ON_TPU, reason="long-seq memory test needs TPU")
def test_flash_long_sequence_8k():
    """seq=8192: dense attention would materialize a 8k x 8k f32 score
    matrix per head (256 MB x heads); flash streams KV tiles and must
    run fwd+bwd within VMEM/HBM budget."""
    q, k, v = _rand_qkv(b=1, h=4, s=8192, d=64)
    q = q.astype(jnp.bfloat16)
    k = k.astype(jnp.bfloat16)
    v = v.astype(jnp.bfloat16)
    scale = 0.125

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, scale).astype(
            jnp.float32))

    loss, grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, k, v)
    assert np.isfinite(float(loss))
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


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
