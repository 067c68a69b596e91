"""ISSUE 33: the paged latent-attention (MLA) decode kernel under the
Pallas interpreter, against `mla_attend_absorbed` over the densely
gathered rows — the path it replaces on a TPU and the CPU's own.

One pool of `[c_kv | k_rope]` rows is key and value to every head;
the kernel walks each sequence's live page groups through its block
table. Groups are 128 rows here (the tile's size is set small, as
`TestPagedKernelPageGroups` gets it from the block), so a table of 20
columns at block 16 is one the group does not divide (padded to 24:
three groups); one test runs the tile the cells' shape gives.
"""
import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.nn.pallas import paged_attention as pa
from paddle_tpu.inference.serving.kv_cache import NULL_BLOCK
from paddle_tpu.text.models import mla

BS, MAXB, N = 16, 20, 96
# 1 token; one short of a page, a page; one short of a group, a
# group, two tokens into the second; inside the last page; the full
# table; an inactive slot (table all NULL, one "token")
LENS = (1, 15, 16, 127, 128, 130, 307, 320, 1)
INACTIVE = len(LENS) - 1
TOL = {"float32": 3e-6, "bfloat16": 0.04}


def _cfg(heads):
    # rows of 64 + 16 = 80 values, stored in 128
    return types.SimpleNamespace(
        num_heads=heads, kv_lora_rank=64, qk_nope_head_dim=24,
        qk_rope_head_dim=16, v_head_dim=8)


def _inputs(dtype, heads, lens=LENS, maxb=MAXB, n=N, row=128, layers=1):
    cfg = _cfg(heads)
    rng = np.random.RandomState(32)
    b = len(lens)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    q_nope = draw(b, heads, cfg.qk_nope_head_dim)
    q_rope = draw(b, heads, cfg.qk_rope_head_dim)
    ap = {"wkv_b": draw(cfg.kv_lora_rank, heads * (
        cfg.qk_nope_head_dim + cfg.v_head_dim), scale=0.3)}
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    pool = np.zeros((layers * n, BS, row), np.float32)
    # every page holds finite rows, live or not: what an earlier
    # sequence left behind
    pool[:, :, :width] = rng.randn(layers * n, BS, width)
    tables = np.full((b, maxb), NULL_BLOCK, np.int32)
    nxt = 1
    for i, length in enumerate(lens):
        if i == INACTIVE and lens is LENS:
            continue
        used = -(-length // BS)
        tables[i, :used] = nxt + np.arange(used)
        nxt += used
    assert nxt <= n
    return (cfg, q_nope, q_rope, jnp.asarray(pool, dtype),
            jnp.asarray(tables), jnp.asarray(np.array(lens, np.int32)), ap)


def _dense(cfg, q_nope, q_rope, pool, tables, lens, ap):
    ctx = pool[tables].reshape(tables.shape[0], -1, pool.shape[-1])
    return mla.mla_attend_absorbed(q_nope, q_rope, ctx, lens, ap, cfg)


def _paged(cfg, q_nope, q_rope, pool, tables, lens, ap):
    return mla.mla_attend_paged(q_nope, q_rope, pool, tables, lens, ap,
                                cfg, interpret=True)


def _close(got, want, dtype):
    assert got.dtype == want.dtype and got.shape == want.shape
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.fixture
def small_groups(monkeypatch):
    """128 rows a group: 8 pages of 16."""
    monkeypatch.setattr(pa, "_TILE_BYTES", 1)
    assert pa._pages_per_group(BS, 256) == 8


@pytest.mark.parametrize("heads", [20, 64])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_parity_at_every_edge(small_groups, dtype, heads):
    """Mixed lengths in one batch: every boundary of a page and of a
    group, the full table, NULL-padded tables and an inactive slot;
    H = 20 is padded to the dtype's sublane tile, H = 64 is not."""
    args = _inputs(dtype, heads)
    _close(_paged(*args), _dense(*args), dtype)


@pytest.mark.parametrize("length", [1, 127, 128, 129, 320])
def test_each_length_alone(small_groups, length):
    """A batch of one: the sequence that starts the copies is the
    one that waits for them, with no neighbour's last group to start
    its first."""
    args = _inputs("float32", 20, lens=(length,))
    _close(_paged(*args), _dense(*args), "float32")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dead_rows_never_count(small_groups, dtype):
    """The rows past each context (in the last live page, in the
    dead pages of a live group, in whole dead groups, in the padded
    table columns, in the NULL block all of those point at) hold
    other finite values: the output is the same to the bit."""
    cfg, q_nope, q_rope, pool, tables, lens, ap = _inputs(dtype, 20)
    live = np.zeros((N, BS), bool)
    bt = np.asarray(tables)
    for b, length in enumerate(LENS):
        if b != INACTIVE:
            for pos in range(length):
                live[bt[b, pos // BS], pos % BS] = True
    assert not live[NULL_BLOCK].any()
    stale = jnp.where(jnp.asarray(live)[:, :, None], pool,
                      jnp.asarray(-3e4, pool.dtype))
    out = _paged(cfg, q_nope, q_rope, pool, tables, lens, ap)
    out2 = _paged(cfg, q_nope, q_rope, stale, tables, lens, ap)
    keep = np.arange(len(LENS)) != INACTIVE   # its one row IS the NULL block's
    np.testing.assert_array_equal(np.asarray(out, np.float32)[keep],
                                  np.asarray(out2, np.float32)[keep])


def test_every_attention_reads_its_own_blocks(small_groups):
    """The runner's rule: the pool of A attentions is one run of
    A * N blocks and attention `a` shifts the tables by `a * N`."""
    layers = 3
    cfg, q_nope, q_rope, pool, tables, lens, ap = _inputs(
        "float32", 20, layers=layers)
    outs = []
    for a in range(layers):
        got = _paged(cfg, q_nope, q_rope, pool, tables + a * N, lens, ap)
        want = _dense(cfg, q_nope, q_rope, pool[a * N:(a + 1) * N],
                      tables, lens, ap)
        _close(got, want, "float32")
        outs.append(np.asarray(got))
    assert not np.allclose(outs[0], outs[1])


@pytest.mark.parametrize("q_scale,kv_scale,nope,rope", [
    (1.0, 1.0, 24, 16),                 # GLM-4.7-Flash: no constants
    (2.0, 12 ** 0.5, 16, 8),            # LongCat-Flash: query x 2, the
                                        # latent x (hidden / rank)^0.5
], ids=["glm47f", "longcat"])
def test_both_models_scale_constants(small_groups, q_scale, kv_scale, nope,
                                     rope):
    """The constants live in the query and in the cached row, the
    softmax scale is each model's own `(nope + rope)^-0.5`: rows and
    queries at LongCat's magnitudes (scores x 7) meet the same
    parity as GLM's."""
    cfg, q_nope, q_rope, pool, tables, lens, ap = _inputs("float32", 20)
    cfg.qk_nope_head_dim, cfg.qk_rope_head_dim = nope, rope
    q_nope, q_rope = q_nope[..., :nope] * q_scale, q_rope[..., :rope] * q_scale
    ap = {"wkv_b": ap["wkv_b"].reshape(cfg.kv_lora_rank, 20, -1)[
        ..., :nope + cfg.v_head_dim].reshape(cfg.kv_lora_rank, -1)}
    # the stored row is [latent | rope | zeros]: shorter rope, more zeros
    width = cfg.kv_lora_rank + rope
    pool = pool.at[..., :cfg.kv_lora_rank].multiply(kv_scale) \
        .at[..., width:].set(0)
    args = (cfg, q_nope, q_rope, pool, tables, lens, ap)
    _close(_paged(*args), _dense(*args), "float32")


@pytest.mark.parametrize("dtype,heads", [("bfloat16", 20), ("bfloat16", 64),
                                         ("float32", 20)])
def test_the_cells_tile(dtype, heads):
    """Rows of 640 at block 16, as both MLA cells store them: the
    group the shape gives (512 rows in bf16), contexts around its
    boundary, a table of 84 columns that it does not divide."""
    rows = pa._pages_per_group(BS, 640 * jnp.dtype(dtype).itemsize) \
        * BS
    lens = (1, rows - 1, rows, rows + 1, 84 * BS)
    args = _inputs(dtype, heads, lens=lens, maxb=84, n=256, row=640)
    _close(_paged(*args), _dense(*args), dtype)


@pytest.mark.parametrize("block,row_bytes,pages", [
    (16, 1280, 32),     # the cells': 512 rows of 640 bf16 values
    (16, 2560, 16),     # the same rows in f32: 256
    (16, 256, 64),      # short rows: 1024 rows and no more
    (128, 1280, 4),
    (4, 512, 64),       # small blocks: 64 copies a group and no more
    (16, 16384, 8),     # long rows: 128 rows and no fewer
])
def test_group_size_follows_the_shape(block, row_bytes, pages):
    assert pa._pages_per_group(block, row_bytes) == pages


# sha256 of `str(jax.make_jaxpr(...))` at both cells' rows (20 heads, 640
# values), taken on the parent commit (2c397c0): the K/V kernel's walk of
# a window shares the copy helpers with this kernel and leaves its program
# as it was
LATENT_JAXPRS = {"float32": "673f2ee4733e472c",
                 "bfloat16": "57bab619f6c0bbfe"}


@pytest.mark.parametrize("dtype", list(LATENT_JAXPRS))
def test_the_kernel_traces_to_the_program_it_had(dtype):
    text = str(jax.make_jaxpr(
        lambda q, p, t, n: pa.paged_latent_attention(q, p, t, n,
                                                     sm_scale=0.3))(
        jnp.zeros((3, 20, 640), dtype), jnp.zeros((24, BS, 640), dtype),
        jnp.zeros((3, 5), jnp.int32), jnp.ones((3,), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == LATENT_JAXPRS[dtype]


def test_pool_and_query_rows_must_agree():
    with pytest.raises(ValueError, match="row"):
        pa.paged_latent_attention(
            jnp.zeros((2, 4, 128)), jnp.zeros((8, 16, 256)),
            jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32),
            interpret=True)
