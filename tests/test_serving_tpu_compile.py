"""ISSUE 26: the serving programs compiled FOR THE TPU, without one.

The CPU cannot see what made the old decode program move 12 GB a
step: the device layout of the KV pools. With head_dim (64) as the
pools' minor dimension the TPU put the block axis on the lanes and
every program transposed pools on the way in and out. These tests
compile the four pool-writing programs for a described v5e at the
real widths (hidden 1024, 16 heads x 64, block 16; depth, vocabulary
and pool cut down — nothing is allocated) and hold them to: outputs
alias both donated pools, temporaries under half of ONE pool.

All in one file, the topology in a fixture: only the xdist worker
that runs this file loads libtpu (on-chip-measurement guide, s. 2).
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.inference.serving import model_runner as mr

L, HID, HEADS, VOCAB, SEQ, BS, B, T = 2, 1024, 16, 512, 1024, 16, 8, 4
BLOCKS, MAXB = 8192, SEQ // BS
KW = dict(n_head=HEADS, eps=1e-5, block_size=BS)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _programs(sd):
    i32, f32 = jnp.int32, jnp.float32
    blocks = dict(
        ln1_w=(HID,), ln1_b=(HID,), qkv_w=(HID, 3 * HID),
        qkv_b=(3 * HID,), proj_w=(HID, HID), proj_b=(HID,),
        ln2_w=(HID,), ln2_b=(HID,), fc1_w=(HID, 4 * HID),
        fc1_b=(4 * HID,), fc2_w=(4 * HID, HID), fc2_b=(HID,))
    params = dict(
        wte=sd((VOCAB, HID)), wpe=sd((SEQ, HID)), lnf_w=sd((HID,)),
        lnf_b=sd((HID,)),
        blocks={k: sd((L,) + s) for k, s in blocks.items()})
    pool = sd((L, BLOCKS, BS, HID))
    batch = (sd((B, MAXB), i32), sd((B,), i32), sd((B,), f32),
             sd((B,), i32))
    one = (sd((MAXB,), i32), sd((), f32), sd((), i32), sd((), i32))
    return pool, {
        "decode": (mr.decode_step, (3, 4), (
            params, sd((B,), i32), sd((B,), i32), pool, pool, *batch,
            sd((B,), i32))),
        "verify": (mr.verify_step, (3, 4), (
            params, sd((B, T), i32), sd((B,), i32), pool, pool,
            *batch, sd((B, T), i32))),
        "tail": (mr.prefill_tail_step, (4, 5), (
            params, sd((1, 64), i32), sd((), i32), sd((), i32), pool,
            pool, *one)),
        "prefill": (mr.prefill_step, (3, 4), (
            params, sd((1, 160), i32), sd((), i32), pool, pool, *one)),
    }


@pytest.mark.parametrize("program",
                         ["decode", "verify", "tail", "prefill"])
def test_tpu_program_updates_pools_in_place(one_chip, program):
    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool, programs = _programs(sd)
    fn, donated, args = programs[program]
    mem = jax.jit(functools.partial(fn, **KW), donate_argnums=donated) \
        .lower(*args).compile().memory_analysis()
    one_pool = pool.size * pool.dtype.itemsize
    assert mem.alias_size_in_bytes >= 2 * one_pool
    assert mem.temp_size_in_bytes < one_pool // 2, (
        f"{mem.temp_size_in_bytes / one_pool:.2f} of one pool")


# ---------------------------------------------------------------------------
# ISSUE 27: the latent (MLA) runner's programs, GLM-4.7-Flash's widths
# ---------------------------------------------------------------------------

def _mla_programs(sd):
    """decode and one prefill of `mla_runner` at the published widths
    (hidden 2048, 20 heads, ranks 768/512, 64 experts of 1536, block
    16, batch 64, 4096 positions); depth 1 + 2 layers, vocabulary and
    pool cut down. The pool's row is what `MLARunner` stores."""
    from paddle_tpu.inference.serving import mla_runner as mla
    from paddle_tpu.text.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                                      Glm4MoeLiteModel)

    cfg = Glm4MoeLiteConfig(num_hidden_layers=3, vocab_size=2048,
                            dtype="bfloat16")
    shapes = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda p: p._value, Glm4MoeLiteModel(cfg)._tree))
    params = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype), shapes)
    row = -(-cfg.latent_row // 128) * 128
    i32, f32, bsz, maxb = jnp.int32, jnp.float32, 64, 4096 // 16
    pool = sd((3, 16385, 16, row), jnp.bfloat16)
    kw = dict(cfg=cfg, layers=Glm4MoeLiteModel.mla_layers, block_size=16)
    return pool, {
        "decode": (mla.decode_step, (
            params, sd((bsz,), i32), sd((bsz,), i32), (pool,),
            sd((bsz, maxb), i32), sd((bsz,), i32), sd((bsz,), f32),
            sd((bsz,), i32), sd((bsz,), i32))),
        "prefill": (mla.prefill_step, (
            params, sd((1, 1536), i32), sd((), i32), (pool,),
            sd((maxb,), i32), sd((), f32), sd((), i32), sd((), i32))),
    }, kw


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_tpu_latent_program_updates_its_pool_in_place(one_chip, program):
    """The one pool is aliased input to output, and the temporaries
    together are smaller than the pool and than one layer's routed
    experts (0.75 GiB: what slicing a layer out of the stack would
    copy), so neither is copied."""
    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool, programs, kw = _mla_programs(sd)
    fn, args = programs[program]
    mem = jax.jit(functools.partial(fn, **kw), donate_argnums=(3,)) \
        .lower(*args).compile().memory_analysis()
    one_pool = pool.size * pool.dtype.itemsize
    one_layers_experts = 64 * 2048 * 3072 * 2
    assert mem.alias_size_in_bytes >= one_pool
    assert mem.temp_size_in_bytes < min(one_pool, one_layers_experts), (
        f"{mem.temp_size_in_bytes / 2 ** 20:.0f} MiB of temporaries")


# ---------------------------------------------------------------------------
# ISSUE 28: the sampler's switch survives the TPU compiler
# ---------------------------------------------------------------------------

def test_tpu_sampler_keeps_its_switch_and_sorts_in_one_branch(one_chip):
    """At GLM-4.7-Flash's batch and vocabulary the optimised HLO still
    holds the `conditional` with its three branch computations (not
    flattened into a select that runs them all), and the one sort of
    [64, 154880] values, without an index payload, lies in a branch
    and not in the entry computation that every batch runs."""
    import re

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = jax.jit(mr.sample_tokens).lower(
        sd((64, 154880)), sd((64,)), sd((64,), jnp.int32),
        sd((64,), jnp.uint32)).compile().as_text()
    (switch,) = re.findall(r" conditional\(.*branch_computations=\{(.*?)\}",
                           hlo)
    branches = [b.strip().lstrip("%") for b in switch.split(",")]
    assert len(branches) == 3
    body = {}          # computation name -> its text
    for block in hlo.split("\n\n"):
        head = block.lstrip().split("\n", 1)[0]
        name = re.match(r"(?:ENTRY )?%?([\w.\-]+) ", head)
        if name:
            body[name.group(1)] = block
    entry, = [b for b in body.values() if b.lstrip().startswith("ENTRY")]
    big = r"= f32\[64,154880\]\S* sort\("
    assert not re.search(r" sort\(", entry)
    assert not re.search(r" sort\(", body[branches[0]])
    assert not re.search(r" sort\(", body[branches[1]])
    assert len(re.findall(big, body[branches[2]])) == 1


# ---------------------------------------------------------------------------
# ISSUE 30: decode and verify attend through the block tables
# ---------------------------------------------------------------------------

# a gathered context, in any of the forms the dense reference gives it:
# [B, MAXB*BS, H, D], [B, MAXB*BS, H*D], [B*MAXB, BS, H*D], [B, MAXB, BS, ...]
_CELL_B = 32


def _dense_context(width):
    b = _CELL_B
    return rf"\[({b},{width * BS},\d|{b * width},{BS},|{b},{width},{BS},)"


def _cell_program(sd, program, pool_dtype):
    """decode / verify at the offline cell's widths: hidden 1024, 16
    heads x 64, block 16, batch 32, table 64 (65 for verify: the
    engine's NULL column); depth, vocabulary and pool cut down."""
    pool, programs = _programs(sd)
    fn, donated, args = programs[program]
    i32 = jnp.int32
    pool = sd(pool.shape, pool_dtype)
    width = MAXB + (program == "verify")
    ids = (_CELL_B, T) if program == "verify" else (_CELL_B,)
    args = (args[0], sd(ids, i32), sd((_CELL_B,), i32), pool, pool,
            sd((_CELL_B, width), i32), sd((_CELL_B,), i32),
            sd((_CELL_B,)), sd((_CELL_B,), i32), sd(ids, i32))
    return fn, donated, args, pool, _dense_context(width)


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("program", ["decode", "verify"])
def test_tpu_kernel_program_gathers_no_context(one_chip, program,
                                               pool_dtype):
    """With the Pallas kernel as its attention the program compiled
    for the v5e aliases both pools, keeps under 64 MiB of temporaries
    (the dense reference: 0.4 GiB of gathered contexts), holds the
    Mosaic call and no op over a gathered context; the dense program
    does hold such ops, so the pattern can see them."""
    import re

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, donated, args, pool, context = _cell_program(
        sd, program, pool_dtype)

    def compiled(use_kernel):
        return jax.jit(
            functools.partial(fn, use_kernel=use_kernel, **KW),
            donate_argnums=donated).lower(*args).compile()

    kernel = compiled(True)
    mem = kernel.memory_analysis()
    one_pool = pool.size * pool.dtype.itemsize
    assert mem.alias_size_in_bytes >= 2 * one_pool
    assert mem.temp_size_in_bytes < 64 * 2 ** 20, (
        f"{mem.temp_size_in_bytes / 2 ** 20:.0f} MiB of temporaries")
    hlo = kernel.as_text()
    assert "tpu_custom_call" in hlo
    assert not re.search(context, hlo), \
        re.findall(context + r".*", hlo)[:3]
    if pool_dtype == jnp.float32:
        dense = compiled(False)
        assert re.search(context, dense.as_text())
        assert dense.memory_analysis().temp_size_in_bytes \
            > 4 * mem.temp_size_in_bytes


# ---------------------------------------------------------------------------
# ISSUE 31: the same runner's programs at LongCat-Flash's widths
# ---------------------------------------------------------------------------

def _longcat_programs(sd):
    """Decode and the longest prefill of `serve-longcat-offline-decode`
    as the cell runs them: hidden 6144, 64 heads, ranks 1536/512, the
    768-wide router, 16 held experts of 2048, 4 double layers = 8
    attentions' rows, batch 64, 4096 positions, 16385 blocks, the
    vocabulary's slice."""
    from paddle_tpu.inference.serving import mla_runner as mla
    from paddle_tpu.text.models.longcat_flash import (LongcatFlashConfig,
                                                      LongcatFlashModel)

    cfg = LongcatFlashConfig(num_layers=4, vocab_size=16384,
                             experts_held=16, dtype="bfloat16")
    shapes = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda p: p._value, LongcatFlashModel(cfg)._tree))
    params = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype), shapes)
    i32, f32, bsz, maxb = jnp.int32, jnp.float32, 64, 4096 // 16
    pool = sd((8, 16385, 16, 640), jnp.bfloat16)
    kw = dict(cfg=cfg, layers=LongcatFlashModel.mla_layers, block_size=16)
    return pool, {
        "decode": (mla.decode_step, (
            params, sd((bsz,), i32), sd((bsz,), i32), (pool,),
            sd((bsz, maxb), i32), sd((bsz,), i32), sd((bsz,), f32),
            sd((bsz,), i32), sd((bsz,), i32))),
        "prefill": (mla.prefill_step, (
            params, sd((1, 2048), i32), sd((), i32), (pool,),
            sd((maxb,), i32), sd((), f32), sd((), i32), sd((), i32))),
    }, kw


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_tpu_longcat_programs_fit_the_cell(one_chip, program):
    """Decode (the dense path, the CPU's) and the longest prefill,
    compiled for the v5e: the one pool is aliased, the temporaries
    are smaller than the pool (decode's also than one layer's held
    experts, 1.13 GiB: what slicing a layer out of the stack would
    copy; the prefill's 2.1 GiB are 64 heads' scores over 2048 x
    2048 and 24576 rows of assignments), and arguments and
    temporaries together fit the chip's 15.75 GiB."""
    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool, programs, kw = _longcat_programs(sd)
    fn, args = programs[program]
    mem = jax.jit(functools.partial(fn, **kw), donate_argnums=(3,)) \
        .lower(*args).compile().memory_analysis()
    one_pool = pool.size * pool.dtype.itemsize
    one_layers_experts = 16 * 6144 * 6144 * 2
    gib = 2 ** 30
    assert mem.alias_size_in_bytes >= one_pool
    limit = min(one_pool, one_layers_experts) if program == "decode" \
        else one_pool
    assert mem.temp_size_in_bytes < limit, (
        f"{mem.temp_size_in_bytes / gib:.2f} GiB of temporaries")
    assert 12.0 * gib < mem.argument_size_in_bytes < 12.3 * gib
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * gib


# ---------------------------------------------------------------------------
# ISSUE 33: the latent decode attends through the block tables
# ---------------------------------------------------------------------------

def _digest(text):
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


_LATENT = {"glm47f": _mla_programs, "longcat": _longcat_programs}
# any form of 64 sequences' 4096 gathered rows of 640
_LATENT_CONTEXT = r"\[(64,4096,640|64,256,16,640|16384,16,640)\]"


@pytest.mark.parametrize("family", ["glm47f", "longcat"])
def test_tpu_latent_kernel_program_gathers_no_context(one_chip, family):
    """GLM-4.7-Flash's and LongCat-Flash's decode at the cells'
    shapes (batch 64, table 256, rows of 640, 20 and 64 heads) with
    the Pallas latent kernel as the attention, compiled for the v5e:
    the pool is aliased, the temporaries are under 64 MiB (the dense
    path: 0.31 GiB of gathered context a layer), the Mosaic call is
    there and no op over a gathered context, and the program fits
    the chip; the dense program does hold such ops, so the pattern
    can see them."""
    import re

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool, programs, kw = _LATENT[family](sd)
    fn, args = programs["decode"]

    def compiled(use_kernel):
        return jax.jit(functools.partial(fn, use_kernel=use_kernel, **kw),
                       donate_argnums=(3,)).lower(*args).compile()

    kernel = compiled(True)
    mem = kernel.memory_analysis()
    assert mem.alias_size_in_bytes >= pool.size * pool.dtype.itemsize
    assert mem.temp_size_in_bytes < 64 * 2 ** 20, (
        f"{mem.temp_size_in_bytes / 2 ** 20:.0f} MiB of temporaries")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    hlo = kernel.as_text()
    assert "tpu_custom_call" in hlo
    assert not re.search(_LATENT_CONTEXT, hlo), \
        re.findall(_LATENT_CONTEXT + r".*", hlo)[:3]
    dense = compiled(False)
    assert re.search(_LATENT_CONTEXT, dense.as_text())
    assert dense.memory_analysis().temp_size_in_bytes \
        > 4 * mem.temp_size_in_bytes


@pytest.mark.parametrize("family,want", [
    ("glm47f", "df3c6107807bfa41"), ("longcat", "f70e064758a4b937")])
def test_tpu_latent_dense_program_is_the_parents(one_chip, family, want):
    """`use_kernel=False` is the CPU's path and the reference the
    kernel is tested against: its decode program lowers, for the v5e
    at these shapes, to the text it lowered to before the kernel
    came (fce49e0; a digest of that text, which holds no path)."""
    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _, programs, kw = _LATENT[family](sd)
    fn, args = programs["decode"]
    text = jax.jit(functools.partial(fn, **kw), donate_argnums=(3,)) \
        .lower(*args).as_text()
    assert "tpu_custom_call" not in text
    assert _digest(text) == want


@pytest.mark.parametrize("program,pool_dtype,want", [
    ("decode", jnp.float32, "66cec4f467292521"),
    ("decode", jnp.bfloat16, "501ff35cf8d196ca"),
    ("verify", jnp.float32, "b92440df44b4b524"),
    ("verify", jnp.bfloat16, "79f1544c60a784bf")],
    ids=["decode-f32", "decode-bf16", "verify-f32", "verify-bf16"])
def test_gpt2_kernel_programs_are_the_parents(program, pool_dtype, want):
    """The second kernel shares `paged_attention.py` with GPT-2's:
    GPT-2's decode and verify at the offline cell's shapes, with
    their kernel, trace to the jaxpr they traced to before (fce49e0;
    the kernel's body is in it; the lowered text also holds the
    checkout's path and line numbers, the jaxpr does not)."""
    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    fn, _, args, _, _ = _cell_program(sd, program, pool_dtype)
    jaxpr = jax.make_jaxpr(
        functools.partial(fn, use_kernel=True, **KW))(*args)
    assert "pallas_call" in str(jaxpr)
    assert _digest(str(jaxpr)) == want


# ---------------------------------------------------------------------------
# ISSUE 34: K/V pools with grouped heads + per-slot state, at LFM2's widths
# ---------------------------------------------------------------------------

def _lfm2_programs(sd):
    """Decode (through the paged kernel, 32 query heads over 8 K/V
    heads) and the longest prefill of `serve-lfm2-offline-decode` as
    the cell runs them: hidden 2048, the published layers 0-9 (8
    short convolutions, 2 attentions; 2 dense FFNs of 11776, 8 x 64
    experts of 1536), the whole vocabulary, batch 256, 4096
    positions, 65537 blocks, the state of 256 slots."""
    from paddle_tpu.inference.serving import state_runner as sr
    from paddle_tpu.text.models.lfm2_moe import (PUBLISHED_LAYER_TYPES,
                                                 Lfm2MoeConfig, Lfm2MoeModel)

    cfg = Lfm2MoeConfig(num_hidden_layers=10,
                        layer_types=PUBLISHED_LAYER_TYPES[:10],
                        dtype="bfloat16")
    shapes = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda p: p._value, Lfm2MoeModel(cfg)._tree))
    params = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype), shapes)
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    bsz, maxb = 256, 4096 // 16
    pools = (sd((2, 65537, 16, 512), bf16), sd((2, 65537, 16, 512), bf16),
             sd((8, bsz, 2, 2048), bf16))
    kw = dict(cfg=cfg, model=Lfm2MoeModel, block_size=16)
    return pools, {
        "decode": (functools.partial(sr.decode_step, use_kernel=True), (
            params, sd((bsz,), i32), sd((bsz,), i32), pools,
            sd((bsz, maxb), i32), sd((bsz,), i32), sd((bsz,), f32),
            sd((bsz,), i32), sd((bsz,), i32))),
        "prefill": (sr.prefill_step, (
            params, sd((1, 2048), i32), sd((), i32), pools,
            sd((maxb,), i32), sd((), f32), sd((), i32), sd((), i32),
            sd((), i32))),
    }, kw


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_tpu_lfm2_programs_fit_the_cell(one_chip, program):
    """Compiled for the v5e: both pools AND the per-slot state are
    aliased (no second copy among the temporaries, which stay under
    a quarter of a GiB: the prefill's scores are blocked by 512
    queries, 128 MiB), the weights are 9.81 GiB, and arguments and
    temporaries together fit the chip's 15.75 GiB with the pools and
    the state at the cell's size. Decode holds the Mosaic call."""
    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools, programs, kw = _lfm2_programs(sd)
    fn, args = programs[program]
    compiled = jax.jit(functools.partial(fn, **kw), donate_argnums=(3,)) \
        .lower(*args).compile()
    mem = compiled.memory_analysis()
    held = sum(p.size * p.dtype.itemsize for p in pools)
    gib = 2 ** 30
    assert 4.0 * gib < held < 4.03 * gib
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 0.25 * gib, (
        f"{mem.temp_size_in_bytes / gib:.2f} GiB of temporaries")
    assert 13.8 * gib < mem.argument_size_in_bytes < 13.9 * gib
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * gib
    if program == "decode":
        hlo = compiled.as_text()
        assert "tpu_custom_call" in hlo
        # no sequence's context is gathered: [256, 4096, ...] nowhere
        assert "[256,4096," not in hlo and "[65536,16," not in hlo


# ---------------------------------------------------------------------------
# ISSUE 35: the routed experts' grouped matmul in the repo's own kernel
# ---------------------------------------------------------------------------

_EXPERT = {
    # family: (programs, temporaries' limit of the fit tests above in
    # MiB, one layer's routed experts in bytes)
    "glm47f": (_mla_programs, 64, 64 * 2048 * 3072 * 3),
    "longcat": (_longcat_programs, 64, 16 * 6144 * 2048 * 6),
    "lfm2": (_lfm2_programs, 256, 64 * 2048 * 1536 * 6),
}


@pytest.mark.parametrize("family", list(_EXPERT))
def test_tpu_expert_decode_multiplies_groups_in_the_kernel(
        one_chip, family, monkeypatch):
    """The three expert families' decode programs at the cells'
    shapes, traced as on a TPU (the platform is the one thing a
    described chip cannot tell the predicate) and compiled for the
    v5e: no `ragged-dot` is left (XLA's carries `ragged_dot_tiling=
    "512,512,512"`), the Mosaic calls are there, pools and state are
    still aliased, the temporaries stay within the fit tests' limits
    and far under one layer's experts (no stack is sliced or copied),
    and the program fits the chip. The longest prefill bucket takes
    the kernel too where its rows fit in VMEM beside a block of
    weights. (Traced on the CPU every program keeps `ragged_dot`:
    the digests above.)"""
    from paddle_tpu.incubate.nn import pallas

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    make, temp_mib, one_layers_experts = _EXPERT[family]
    pools, programs, kw = make(sd)
    pools = pools if isinstance(pools, tuple) else (pools,)

    def lowered(program, **more):
        fn, args = programs[program]
        return jax.jit(functools.partial(fn, **more, **kw),
                       donate_argnums=(3,)).lower(*args)

    more = {} if family == "lfm2" else {"use_kernel": True}
    # traced as here, on the CPU, the layer keeps `ragged_dot`
    text = lowered("decode", **more).as_text()
    assert "ragged_dot" in text and "grouped_matmul" not in text
    monkeypatch.setattr(pallas, "_on_tpu", lambda: True)
    text = lowered("decode", **more).as_text()
    assert "grouped_matmul" in text and "ragged_dot" not in text
    compiled = lowered("decode", **more).compile()
    hlo = compiled.as_text()
    assert "ragged" not in hlo and "tpu_custom_call" in hlo
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools)
    assert mem.temp_size_in_bytes < temp_mib * 2 ** 20, (
        f"{mem.temp_size_in_bytes / 2 ** 20:.0f} MiB of temporaries")
    assert mem.temp_size_in_bytes < one_layers_experts / 4
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    # the longest prefill bucket: 8192 rows of 2048 values fit in
    # VMEM beside a block of weights, LongCat's 24 576 of 6144 do not
    prefill = lowered("prefill").as_text()
    if family == "longcat":
        assert "ragged_dot" in prefill and "grouped_matmul" not in prefill
    else:
        assert "ragged_dot" not in prefill and "grouped_matmul" in prefill
        mem = lowered("prefill").compile().memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 15.75 * 2 ** 30



@pytest.mark.parametrize("shape,sk,dtype,causal,want", [
    ((12, 16, 1024, 64), 1024, jnp.bfloat16, True, "resident"),  # the cells
    ((2, 16, 4096, 128), 4096, jnp.bfloat16, True, "streamed"),  # too long
    ((1, 4, 2048, 256), 2048, jnp.bfloat16, True, "resident"),   # > 16 MiB
    ((1, 4, 8192, 64), 8192, jnp.bfloat16, True, "streamed"),
    ((2, 4, 4096, 64), 4096, jnp.float32, False, "streamed"),
    # key blocks past the last query: dkv's index maps stay in range
    ((1, 4, 4096, 64), 8192, jnp.bfloat16, True, "streamed"),
], ids=["cell", "4k-d128", "2k-d256", "8k-streamed", "4k-f32-streamed",
        "4k-x-8k-streamed"])
def test_tpu_flash_kernels_compile_by_shape(one_chip, shape, sk, dtype,
                                            causal, want):
    """ISSUE 37: the three flash kernels at the default blocks, compiled
    by Mosaic for the v5e at real widths (the interpreter checks the
    mathematics, not a slice's alignment or the VMEM a body needs): a
    train step's attention is exactly three Mosaic calls, and the
    operands' bytes alone choose between holding the walked operand
    whole and streaming it."""
    from paddle_tpu.core.monitor import stat_get
    from paddle_tpu.incubate.nn.attention_pallas import flash_attention

    sd = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    kd = jax.ShapeDtypeStruct((*shape[:2], sk, shape[3]), dtype,
                              sharding=one_chip)
    scale = shape[-1] ** -0.5

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, scale)
                       .astype(jnp.float32))

    paths = ("kernels/flash/resident", "kernels/flash/streamed")
    before = [stat_get(p) for p in paths]
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sd, kd, kd).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    moved = {p.rsplit("/", 1)[1]: stat_get(p) - n
             for p, n in zip(paths, before)}
    assert moved == {"resident": 0, "streamed": 0, want: 2}


# ---------------------------------------------------------------------------
# ISSUE 38: window and full attention layers in one grouped cache, at
# Mellum2's widths
# ---------------------------------------------------------------------------

def _mellum_programs(sd):
    """Decode (through the paged kernel: 8 launches, 32 query heads over
    4 K/V heads of 128, six of them over a window of 1024) and the
    longest prefill of `serve-mellum2-offline-decode-8k` as the cell
    runs them: hidden 2304, the published layers 0-7, 8 x 64 experts of
    896, the whole vocabulary, batch 96, 10 240 positions, four cache
    groups' tables, 80 449 blocks of two layers."""
    from paddle_tpu.inference.serving import state_runner as sr
    from paddle_tpu.text.models.mellum import (PUBLISHED_LAYER_TYPES,
                                               MellumConfig, MellumModel,
                                               cache_layout)

    cfg = MellumConfig(num_hidden_layers=8,
                       layer_types=PUBLISHED_LAYER_TYPES[:8],
                       dtype="bfloat16")
    shapes = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda p: p._value, MellumModel(cfg)._tree))
    params = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype), shapes)
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    bsz, maxb, blocks = 96, 10240 // 16, 96 * 838 + 1
    pools = (sd((2, blocks, 16, 512), bf16), sd((2, blocks, 16, 512), bf16))
    kw = dict(cfg=cfg, model=MellumModel, block_size=16,
              layout=cache_layout(cfg))
    return pools, {
        "decode": (functools.partial(sr.decode_step, use_kernel=True), (
            params, sd((bsz,), i32), sd((bsz,), i32), pools,
            sd((4, bsz, maxb), i32), sd((bsz,), i32), sd((bsz,), f32),
            sd((bsz,), i32), sd((bsz,), i32))),
        "prefill": (sr.prefill_step, (
            params, sd((1, 8192), i32), sd((), i32), pools,
            sd((4, maxb), i32), sd((), f32), sd((), i32), sd((), i32))),
    }, kw


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_tpu_mellum_programs_fit_the_cell(one_chip, program, monkeypatch):
    """Compiled for the v5e, traced as on a TPU: both pools are aliased,
    the weights and the pools are 11.98 GiB of arguments, decode's
    temporaries stay under 64 MiB (no sequence's context is gathered,
    no window group's table is expanded) and the longest prefill's under
    1.6 GiB (65 536 assignment rows; a window layer's scores are 512 x
    1536, a full layer's 512 x 8192, a query block), and arguments and
    temporaries together fit the chip's 15.75 GiB. Decode holds the
    Mosaic calls: 8 paged attentions with and without a window, 16
    grouped matmuls."""
    from paddle_tpu.incubate.nn import pallas

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    monkeypatch.setattr(pallas, "_on_tpu", lambda: True)
    pools, programs, kw = _mellum_programs(sd)
    fn, args = programs[program]
    compiled = jax.jit(functools.partial(fn, **kw), donate_argnums=(3,)) \
        .lower(*args).compile()
    mem = compiled.memory_analysis()
    held = sum(p.size * p.dtype.itemsize for p in pools)
    gib = 2 ** 30
    assert 4.9 * gib < held < 4.92 * gib
    assert mem.alias_size_in_bytes >= held
    assert 11.9 * gib < mem.argument_size_in_bytes < 12.0 * gib
    limit = 64 * 2 ** 20 if program == "decode" else 1.6 * gib
    assert mem.temp_size_in_bytes < limit, (
        f"{mem.temp_size_in_bytes / gib:.2f} GiB of temporaries")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * gib
    if program == "decode":
        hlo = compiled.as_text()
        assert hlo.count("tpu_custom_call") >= 24 and "ragged" not in hlo
        assert "[96,10240," not in hlo and "[96,640,16," not in hlo


# ---------------------------------------------------------------------------
# Attention and a Mamba-2 mixer side by side, a float32 state a sequence
# beside the pools, at Falcon-H1-34B's widths
# ---------------------------------------------------------------------------

def _falcon_programs(sd):
    """Decode (the paged kernel at 20 query heads over 4 K/V heads, the
    state kernel) and the longest prefill of `serve-falconh1-offline-
    decode` as the cell runs them: hidden 5120, the published layers 0-3
    (attention and a mixer of 32 heads x 128 over 2 groups of 256 in
    each, a SwiGLU of 21504), the whole vocabulary of 261 120, batch 128,
    4096 positions, 32 769 blocks, the convolution tails in bf16 and the
    states in float32 of 128 slots."""
    from paddle_tpu.inference.serving import state_runner as sr
    from paddle_tpu.text.models.falcon_h1 import (FalconH1Config,
                                                  FalconH1Model)

    cfg = FalconH1Config(num_hidden_layers=4, dtype="bfloat16")
    shapes = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda p: p._value, FalconH1Model(cfg)._tree))
    params = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype), shapes)
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    bsz, maxb = 128, 4096 // 16
    pools = (sd((4, 32769, 16, 512), bf16), sd((4, 32769, 16, 512), bf16),
             sd((4, bsz, 3, 5120), bf16), sd((4, bsz, 32, 256, 128), f32))
    kw = dict(cfg=cfg, model=FalconH1Model, block_size=16,
              states=("window", "ssm"))
    return pools, {
        "decode": (functools.partial(sr.decode_step, use_kernel=True), (
            params, sd((bsz,), i32), sd((bsz,), i32), pools,
            sd((bsz, maxb), i32), sd((bsz,), i32), sd((bsz,), f32),
            sd((bsz,), i32), sd((bsz,), i32))),
        "prefill": (sr.prefill_step, (
            params, sd((1, 2048), i32), sd((), i32), pools,
            sd((maxb,), i32), sd((), f32), sd((), i32), sd((), i32),
            sd((), i32))),
    }, kw


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_tpu_falcon_programs_fit_the_cell(one_chip, program, monkeypatch):
    """Compiled for the v5e, traced as on a TPU: the pools, the
    convolution tails and the 2 GiB of float32 state are aliased (no
    copy among the temporaries), the weights and what the cache holds
    are 14.2 GiB of arguments, and arguments and temporaries together
    fit the chip's 15.75 GiB. Decode holds the Mosaic calls: four state
    updates and four paged attentions, one each a layer."""
    import re

    from paddle_tpu.incubate.nn import pallas

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    monkeypatch.setattr(pallas, "_on_tpu", lambda: True)
    pools, programs, kw = _falcon_programs(sd)
    fn, args = programs[program]
    compiled = jax.jit(functools.partial(fn, **kw), donate_argnums=(3,)) \
        .lower(*args).compile()
    mem = compiled.memory_analysis()
    held = sum(p.size * p.dtype.itemsize for p in pools)
    gib = 2 ** 30
    assert 6.0 * gib < held < 6.02 * gib
    assert mem.alias_size_in_bytes >= held
    assert 14.1 * gib < mem.argument_size_in_bytes < 14.3 * gib
    assert mem.temp_size_in_bytes < 0.6 * gib, (
        f"{mem.temp_size_in_bytes / gib:.2f} GiB of temporaries")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * gib
    if program == "decode":
        hlo = compiled.as_text()
        assert hlo.count('custom_call_target="tpu_custom_call"') == 8
        # the names `ssm_state_roofline.falconh1` reads the launches by
        assert len(re.findall(r"%ssm_state\.\d+ = ", hlo)) == 4
        assert len(re.findall(r"%attend\.\d+ = ", hlo)) == 4
