"""The Pallas grouped matmul of the dropless experts (ISSUE 35),
through the interpreter on the CPU, against `jax.lax.ragged_dot`:
the kernel itself over the group layouts that can go wrong, the
expert layer through it (`first=`, the stacked view with a traced
layer), the tile rule and the predicate. Toy rows: the interpreter
walks every grid step."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.incubate.nn.pallas import grouped_matmul as gm


def _operands(seed, m, groups, k, n, dtype):
    rng = np.random.RandomState(seed)
    rows = jnp.asarray(rng.randn(m, k), dtype)
    w = jnp.asarray(rng.randn(groups, k, n) * 0.2, dtype)
    return rows, w


@pytest.mark.parametrize("m,k,n,sizes,stack,first,dtype", [
    # all groups empty but one; one group takes every row
    (48, 16, 24, [0, 0, 9, 0], 4, 0, jnp.float32),
    (48, 16, 24, [0, 48, 0, 0], 4, 0, jnp.float32),
    (32, 16, 24, [32], 1, 0, jnp.float32),
    # groups that straddle the 16-row tiles; a size-1 group at the
    # last row; a row count that is no multiple of a tile
    (64, 16, 24, [17, 1, 29, 16, 1], 5, 0, jnp.float32),
    (64, 16, 24, [20, 0, 43, 0, 1], 5, 0, jnp.float32),
    (10, 8, 12, [3, 0, 6, 1], 4, 0, jnp.float32),
    # rows behind the last group; no group at all
    (64, 16, 24, [5, 0, 7, 2], 4, 0, jnp.float32),
    (32, 16, 24, [0, 0, 0], 3, 0, jnp.float32),
    # E groups from `first` of a longer stack
    (64, 16, 24, [11, 0, 30, 23], 12, 4, jnp.float32),
    # whole tiles (`_tiles` answers): the three models' w13 and w2,
    # widths / 16, bf16: GLM and LFM2 (2048 -> 2 x 1536 -> 2048),
    # LongCat (6144 -> 2 x 2048 -> 6144)
    (64, 128, 256, [17, 30, 16, 1], 4, 0, jnp.bfloat16),
    (64, 128, 128, [0, 40, 3, 21], 4, 0, jnp.bfloat16),
    (48, 384, 256, [1, 0, 2, 1], 16, 8, jnp.bfloat16),
    (48, 128, 384, [1, 0, 2, 1], 16, 8, jnp.bfloat16),
    # windows of more than one tile, groups of more than one window
    (256, 128, 256, [100, 3, 150, 3], 4, 0, jnp.bfloat16),
])
def test_kernel_equals_ragged_dot(m, k, n, sizes, stack, first, dtype):
    rows, w = _operands(m + len(sizes), m, stack, k, n, dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    full = jnp.zeros((stack,), jnp.int32).at[
        first:first + len(sizes)].set(sizes)
    want = np.asarray(jax.lax.ragged_dot(rows, w, full), np.float32)
    got = gm.grouped_matmul(rows, w, sizes, jnp.int32(first),
                            interpret=True)
    assert got.dtype == rows.dtype and got.shape == (m, n)
    held = int(sizes.sum())
    tol = 2e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(got, np.float32)[:held],
                               want[:held], atol=tol)
    # rows behind the last group: zero, as `ragged_dot` leaves them
    assert not np.asarray(got, np.float32)[held:].any()
    assert not want[held:].any()


def _layer(seed, t=24, h=16, f=8, e=6, k=2, layers=None):
    rng = np.random.RandomState(seed)
    r = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    lead = () if layers is None else (layers,)
    u, w13, w2 = r(t, h), r(*lead, e, h, 2 * f), r(*lead, e, f, h)
    idx, w = dropless.sigmoid_topk_route(u, r(h, e), r(e) * 0.1, k, 1.8)
    return u, idx, w, w13, w2


@pytest.mark.parametrize("case", ["plain", "stacked", "share", "all_held",
                                  "none_held"])
def test_expert_layer_through_the_kernel(case, monkeypatch):
    """`dropless_expert_ffn` under the interpreter takes the kernel by
    itself and gives what `ragged_dot` gives: a layer of its own, a
    layer of a stack by a TRACED index, and a share (`first=`) whose
    absent picks sort behind every held group."""
    calls = []
    real = gm.grouped_matmul
    monkeypatch.setattr(gm, "grouped_matmul", lambda *a, **kw: (
        calls.append(kw), real(*a, **kw))[1])
    if case == "stacked":
        u, idx, w, w13, w2 = _layer(3, layers=3)
        fn = jax.jit(lambda layer: dropless.dropless_expert_ffn(
            u, idx, w, w13, w2, layer))
        args = [(jnp.int32(i),) for i in range(3)]
    else:
        u, idx, w, w13, w2 = _layer(5)
        first = {"plain": None, "share": 2, "all_held": 0,
                 "none_held": 0}[case]
        if case == "share":
            w13, w2 = w13[2:5], w2[2:5]      # experts 2, 3, 4 of 6
        if case == "none_held":
            idx = idx + 100
        fn = functools.partial(dropless.dropless_expert_ffn, u, idx, w,
                               w13, w2, first=first)
        args = [()]
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)
    want = [np.asarray(fn(*a)) for a in args]
    assert not calls
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    if case == "stacked":
        fn = jax.jit(lambda layer: dropless.dropless_expert_ffn(
            u, idx, w, w13, w2, layer))
    got = [np.asarray(fn(*a)) for a in args]
    assert calls and all(kw["interpret"] for kw in calls)
    for g, x in zip(got, want):
        np.testing.assert_allclose(g, x, atol=2e-5)
    if case == "stacked":
        assert not np.allclose(want[0], want[1])
    if case == "none_held":
        assert not got[0].any()


def test_the_kernel_has_no_derivative():
    rows, w = _operands(0, 32, 2, 16, 24, jnp.float32)
    sizes = jnp.asarray([20, 12], jnp.int32)
    with pytest.raises(NotImplementedError, match="no derivative"):
        jax.grad(lambda r: gm.grouped_matmul(
            r, w, sizes, interpret=True).sum())(rows)


@pytest.mark.parametrize("shape,want", [
    # (rows, groups, K, N): the three cells' decode programs, w13
    # then w2: a window twice the rows a group holds, a block of
    # weights of at most 8 MiB
    ((1024, 64, 2048, 3072), (32, 1536)), ((1024, 64, 1536, 2048), (32, 2048)),
    ((256, 64, 2048, 3072), (16, 1536)), ((256, 64, 1536, 2048), (16, 2048)),
    ((768, 16, 6144, 4096), (128, 512)), ((768, 16, 2048, 6144), (128, 2048)),
    # a 2048-token prefill: GLM's and LFM2's 8192 rows fit beside a
    # narrower block, LongCat's 24 576 of 6144 values do not
    ((8192, 64, 2048, 3072), (128, 1024)), ((8192, 64, 1536, 2048), (128, 1024)),
    ((24576, 16, 6144, 4096), None), ((24576, 16, 2048, 6144), None),
    # no whole tiles
    ((1000, 64, 2048, 3072), None), ((1024, 64, 2048, 3000), None),
])
def test_tiles_follow_the_static_shape(shape, want):
    assert gm._tiles(*shape, 2) == want
    if want is not None:
        tm, tn = want
        assert gm._vmem_bytes(shape[0], shape[2], tm, tn, 2) \
            <= gm._VMEM_BYTES


def test_the_predicate_reads_no_switch_of_its_own(monkeypatch):
    """On the CPU the kernel is the interpreter's alone; on a TPU the
    answer is the mesh's, the dtype's and the tiles'."""
    from paddle_tpu.incubate.nn import pallas

    shape = (1024, 64, 2048, 3072)
    ask = gm.grouped_matmul_supported
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)
    assert not ask(*shape, jnp.bfloat16)
    assert not dropless.expert_kernel_supported(256, 4, 64, 2048, 1536,
                                                jnp.bfloat16)
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    assert ask(*shape, jnp.bfloat16) and ask(10, 3, 7, 5, jnp.float32)
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET")
    monkeypatch.setattr(pallas, "_on_tpu", lambda: True)
    assert ask(*shape, jnp.bfloat16)
    assert dropless.expert_kernel_supported(256, 4, 64, 2048, 1536,
                                            jnp.bfloat16)
    assert not ask(*shape, jnp.float32)
    assert not ask(24576, 16, 6144, 4096, jnp.bfloat16)
    # LongCat's longest prefill bucket keeps `ragged_dot` (12 picks a
    # token: 24 576 rows), its decode and the 4-pick models' do not
    assert not dropless.expert_kernel_supported(2048, 12, 16, 6144, 2048,
                                                jnp.bfloat16)
    assert dropless.expert_kernel_supported(64, 12, 16, 6144, 2048,
                                            jnp.bfloat16)
    assert dropless.expert_kernel_supported(2048, 4, 64, 2048, 1536,
                                            jnp.bfloat16)
    monkeypatch.setattr(pallas, "_partitioned", lambda: True)
    assert not ask(*shape, jnp.bfloat16)
