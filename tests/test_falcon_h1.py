"""Falcon-H1 (`falcon_h1`): attention and a Mamba-2 mixer side by side in
every layer, a float32 recurrence state a sequence in the serving cache
beside the paged keys and values and a convolution's tail, and the engine
against the plain float32 reference of the benchmark
(`tpubench/models/falcon_h1.py`) at toy widths with seeded weights on the
CPU.

What is compared with what: (a) the model's own full forward with
`reference_logits`; (b) the chunked scan (`ssm.ssd_chunked`) with the
recurrence one position at a time, across chunk boundaries and with a
padded tail; (c) the Pallas state kernel in the interpreter with
`ssm.ssm_step`; (d) `LLMEngine` (prefill told its slot, then decode
through the pools and both per-slot states) with the reference by
`teacher_forced_deficits`, after a slot's reuse, an eviction and a pool
rebuild, and the same check reading planted faults; (e) the paged kernel at
five query heads a K/V head; (f) the cache's dtype per state array; and
Mellum's programs, pinned.
"""
import functools
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor as cmon
from paddle_tpu.incubate.nn import ssm
from paddle_tpu.incubate.nn.pallas import paged_attention as pa
from paddle_tpu.incubate.nn.pallas import ssm_state
from paddle_tpu.inference.serving import LLMEngine, SamplingParams
from paddle_tpu.inference.serving import model_runner as mr
from paddle_tpu.inference.serving import state_runner
from paddle_tpu.inference.serving.kv_cache import PagedKVCache
from paddle_tpu.monitor import chaos
from paddle_tpu.text.models import falcon_h1 as fh
from paddle_tpu.text.models import mellum as ml

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from tpubench.models import falcon_h1 as fam  # noqa: E402

# 3 layers; 10 query heads over 2 K/V heads (G = 5, as published); 4 SSM
# heads of 16 over 2 groups of 8 state dimensions, 4 taps
TOY = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
           num_hidden_layers=3, num_attention_heads=10,
           num_key_value_heads=2, head_dim=16, mamba_n_heads=4,
           mamba_d_head=16, mamba_d_ssm=64, mamba_n_groups=2,
           mamba_d_state=8, max_position_embeddings=128)
LIMITS = {"logit_margin": 1e-3, "logit_mean_margin": 1e-4}
PROMPT_LENS = (5, 9, 1, 7, 14, 2)     # block 4: none fills its bucket but 1


@pytest.fixture(scope="module")
def toy():
    cfg = fh.FalconH1Config(**TOY)
    paddle.seed(41)
    model = fh.FalconH1ForCausalLM(cfg)
    model.eval()
    params = jax.tree_util.tree_map(lambda p: p._value,
                                    model.model._params_tree())
    return cfg, model, params


def _engine(model, **kw):
    kw = {"max_batch": 4, "block_size": 4, "num_blocks": 64,
          "max_seq_len": 64, **kw}
    return LLMEngine(model, **kw)


def _prompts(cfg, seed=1, lens=PROMPT_LENS):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, cfg.vocab_size, n)) for n in lens]


def _worst(params, cfg, prompts, outs):
    """The largest deficit over the requests (tokens' and means')."""
    return max(float(fam.teacher_forced_deficits(
        params, cfg.num_attention_heads, p, o, 64, cfg=cfg, limits=LIMITS,
        row_bucket=32).max()) for p, o in zip(prompts, outs))


# -- (a) model against reference ------------------------------------------------

def test_reference_equals_the_models_full_forward(toy):
    cfg, model, params = toy
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 48))
    out = model(paddle.to_tensor(ids)).numpy()
    # logits spread by a few units: the check can see a wrong block
    assert 1.0 < out.std() < 10.0
    for row, got in zip(ids, out):
        ref = np.asarray(fam.reference_logits(
            params, jnp.asarray(row), cfg, q_block=16))
        np.testing.assert_allclose(got, ref, atol=2e-4)
    part = np.asarray(fam.reference_logits(
        params, jnp.asarray(ids[0]), cfg, start=30, n_rows=4, q_block=16))
    np.testing.assert_allclose(part, out[0, 30:34], atol=2e-4)


def test_each_branch_adds_to_the_residual(toy):
    """With the published multipliers applied, the draws give the
    attention, the mixer and the SwiGLU each a share of the residual
    stream of the order of the embedding's."""
    cfg, _, params = toy
    lp = params["layers"][0]
    x = fh.embed(params, jnp.arange(1, 33), cfg)
    u = fh.rms_norm(x, lp["ln_in"], cfg.rms_norm_eps)

    def attend(q, k, v, carry, a):
        return fh.attend_dense(q, k, v), carry

    def window(z, carry, c):
        zp = jnp.pad(z, ((3, 0), (0, 0)))
        return jnp.stack([zp[j:j + 32] for j in range(4)], 1), carry

    def scan(x, dt, A, B, C, carry, s):
        return ssm.ssd_chunked(x, dt, A, B, C)[0], carry

    a, _ = fh.attention_operator(u, (), lp["attn"], 0, attend,
                                 jnp.arange(32), cfg)
    m, _ = fh.mamba_operator(u, (), lp["mamba"], 0, window, scan, cfg)
    f = fh.mlp(u, lp["mlp"], cfg)
    rms = [float(jnp.sqrt(jnp.mean(t * t))) for t in (x, a, m, f)]
    assert all(0.2 < r < 5 for r in rms), rms


def test_what_the_config_refuses():
    for bad in (dict(tie_word_embeddings=True),
                dict(mamba_norm_before_gate=True), dict(mamba_rms_norm=False),
                dict(mamba_conv_bias=False), dict(num_key_value_heads=3),
                dict(mamba_d_ssm=48), dict(mamba_n_groups=3)):
        with pytest.raises(ValueError):
            fh.FalconH1Config(**{**TOY, **bad})


# -- (b) the chunked scan against the recurrence ------------------------------------

def _recurrence(x, dt, A, B, C):
    """The recurrence one position at a time (ssm_step), y and the last
    state."""
    state = jnp.zeros((x.shape[1], B.shape[-1], x.shape[2]), jnp.float32)
    ys = []
    for t in range(x.shape[0]):
        y, state = ssm.ssm_step(state, x[t], dt[t], A, B[t], C[t])
        ys.append(y)
    return jnp.stack(ys), state


def _ssm_inputs(t, heads=4, p=16, groups=2, n=8, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(t, heads, p), jnp.float32)
    # Mamba-2's ranges: dt log-uniform in [0.001, 0.1], A in [-16, -1]
    dt = jnp.asarray(np.exp(rng.uniform(-6.9, -2.3, (t, heads))),
                     jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)
    B = jnp.asarray(rng.randn(t, groups, n), jnp.float32)
    C = jnp.asarray(rng.randn(t, groups, n), jnp.float32)
    return x, dt, A, B, C


@pytest.mark.parametrize("t,chunk,length", [
    (1, 128, None), (127, 128, None), (128, 128, None), (129, 128, None),
    (64, 16, None), (48, 16, 37), (32, 128, 5), (40, 8, 1)],
    ids=["1", "127", "128", "129", "4-chunks", "padded-tail",
         "short-prompt", "one-token"])
def test_chunked_scan_equals_the_recurrence(t, chunk, length):
    """y at every real position and the state after the last real one,
    at chunk boundaries and with a padded tail that must not advance the
    state."""
    x, dt, A, B, C = _ssm_inputs(t, seed=t)
    n = t if length is None else length
    want_y, want_state = _recurrence(x[:n], dt[:n], A, B[:n], C[:n])
    y, state = ssm.ssd_chunked(x, dt, A, B, C, chunk=chunk, length=length)
    np.testing.assert_allclose(np.asarray(y[:n]), np.asarray(want_y),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state),
                               atol=1e-5, rtol=1e-5)


# -- (c) the state kernel ---------------------------------------------------------

@pytest.mark.parametrize("heads,groups,n,p", [(4, 2, 8, 16), (32, 2, 16, 128)])
def test_state_kernel_in_the_interpreter_equals_the_step(heads, groups, n, p):
    """One layer of a stacked [L, B, H, N, P] state updated in place by
    the kernel: y and that layer's new states are `ssm_step`'s, every
    other layer is untouched."""
    rng = np.random.RandomState(heads)
    layers, bsz, layer = 3, 5, 1
    state = jnp.asarray(rng.randn(layers, bsz, heads, n, p), jnp.float32)
    x = jnp.asarray(rng.randn(bsz, heads, p), jnp.bfloat16)
    dt = jnp.asarray(np.exp(rng.uniform(-7, 0, (bsz, heads))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)
    B = jnp.asarray(rng.randn(bsz, groups, n), jnp.bfloat16)
    C = jnp.asarray(rng.randn(bsz, groups, n), jnp.bfloat16)
    want_y, want = ssm.ssm_step(state[layer], x, dt, A, B, C)
    y, got = ssm_state.ssm_state_update(state, layer, x, dt, A, B, C,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got[layer]), np.asarray(want),
                               atol=1e-6, rtol=1e-6)
    for other in (0, 2):
        np.testing.assert_array_equal(np.asarray(got[other]),
                                      np.asarray(state[other]))


def test_the_kernel_is_chosen_by_shape_on_a_tpu(monkeypatch):
    from paddle_tpu.incubate.nn import pallas

    monkeypatch.setattr(pallas, "_on_tpu", lambda: True)
    ok = ssm_state.ssm_state_supported
    assert ok(32, 2, 256, 128, jnp.float32)                 # Falcon-H1
    assert not ok(32, 2, 256, 128, jnp.bfloat16)            # a bf16 state
    assert not ok(32, 2, 256, 64, jnp.float32)              # half the lanes
    assert not ok(8, 2, 256, 128, jnp.float32)              # 4 heads a group
    assert not ok(64, 2, 256, 128, jnp.float32)             # 4 MiB a block
    monkeypatch.setattr(pallas, "_on_tpu", lambda: False)
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)
    assert not ok(32, 2, 256, 128, jnp.float32)


# -- (d) engine against reference ------------------------------------------------

@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "kernels-interpreted"])
def test_engine_prefill_then_decode_against_the_reference(
        toy, monkeypatch, interpret):
    """Six requests through four slots (two slots used twice), prompts
    shorter than their bucket and one of a single token; with the
    interpreter, decode through the paged kernel and the state kernel,
    which the counters say."""
    cfg, model, params = toy
    if interpret:
        monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)
    prompts = _prompts(cfg)
    names = ("serve/state/ssm_steps", "serve/state/ssm_steps_kernel",
             "serve/attn/steps")
    before = [cmon.stat_get(n) for n in names]
    eng = _engine(model)
    assert isinstance(eng.runner, state_runner.StateRunner)
    assert isinstance(mr.runner_for(model), state_runner.StateRunner)
    assert eng.use_kernel == interpret
    assert [(p.shape, p.dtype) for p in eng.cache.pools] == [
        ((3, 64, 4, 32), jnp.float32), ((3, 64, 4, 32), jnp.float32),
        ((3, 4, 3, 96), jnp.float32), ((3, 4, 4, 8, 16), jnp.float32)]
    assert cmon.stat_get("serve/state/ssm_bytes_per_seq") == 3 * 4 * 8 * 16 * 4
    assert cmon.stat_get("serve/state/bytes_per_seq") \
        == 3 * 4 * 8 * 16 * 4 + 3 * 3 * 96 * 4
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=20))
    assert [len(o) for o in outs] == [20] * 6
    assert _worst(params, cfg, prompts, outs) <= 1e-3
    ssm_steps, in_kernel, steps = (cmon.stat_get(n) - b
                                   for n, b in zip(names, before))
    assert ssm_steps == 3 * steps > 0
    assert in_kernel == (ssm_steps if interpret else 0)
    assert eng.check_drained() == {}


def _evicting(model, monkeypatch):
    """A pool too small for the load: requests are evicted mid-decode
    and re-admitted (into whatever slot is free) with their output."""
    before = cmon.stat_get("serve/evictions")
    eng = _engine(model, num_blocks=13)
    yield eng
    assert cmon.stat_get("serve/evictions") > before


def _rebuilding(model, monkeypatch):
    """A decode dispatch that fails after consuming the donated pools:
    the engine rebuilds the paged pools AND both slot states and replays
    every running request."""
    eng = _engine(model)
    orig, fired = eng._enqueue_decode, []

    def boom(*arrays):
        if len(fired) == 2:
            for p in eng.cache.pools:
                p.delete()
        fired.append(1)
        if len(fired) == 3:
            raise chaos.XlaRuntimeError(
                "RESOURCE_EXHAUSTED: out of memory (test)")
        return orig(*arrays)

    monkeypatch.setattr(eng, "_enqueue_decode", boom)
    before = cmon.stat_get("serve/pool_resets")
    yield eng
    assert cmon.stat_get("serve/pool_resets") == before + 1
    assert [p.dtype for p in eng.cache.pools[2:]] == [jnp.float32] * 2
    assert not any(p.is_deleted() for p in eng.cache.pools)


def _reusing(model, monkeypatch):
    """One slot: every request decodes where the one before it did."""
    yield _engine(model, max_batch=1)


@pytest.mark.parametrize("how", [_reusing, _evicting, _rebuilding])
def test_no_sequence_is_left_with_anothers_state(toy, monkeypatch, how):
    cfg, model, params = toy
    prompts = _prompts(cfg, seed=4)
    run = how(model, monkeypatch)
    eng = next(run)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=16))
    assert next(run, None) is None
    assert _worst(params, cfg, prompts, outs) <= 1e-3
    assert eng.check_drained() == {}


def _fault_other_slot(eng, mp):
    real = eng.runner.prefill_step
    eng.runner.prefill_step = lambda *a, **kw: real(
        *a[:-1], (a[-1] + 1) % eng.max_batch, **kw)


def _fault_state_not_carried(eng, mp):
    real = ssm.ssm_step
    mp.setattr(ssm, "ssm_step",
               lambda st, *a: real(jnp.zeros_like(st), *a))


def _fault_tail_advances(eng, mp):
    real = ssm.ssd_chunked
    mp.setattr(ssm, "ssd_chunked",
               lambda *a, length=None, **kw: real(*a, **kw))


def _fault_conv_tail_at_bucket_end(eng, mp):
    mp.setattr(state_runner, "_window_tail",
               lambda zp, prompt_len, n: zp[zp.shape[0] - n:])


def _fault_group_modulo(eng, mp):
    """Head h reading B and C of group h % G in place of h // (H / G)."""
    mp.setattr(ssm, "_by_head", lambda bc, heads: jnp.tile(
        bc, (1,) * (bc.ndim - 2) + (heads // bc.shape[-2], 1)))


def _fault_no_key_multiplier(eng, mp):
    """The keys not scaled by key_multiplier, planted as keys' columns
    of W_qkv divided by it."""
    cfg = fh.FalconH1Config(**TOY)
    lo = cfg.num_attention_heads * cfg.head_dim
    scale = jnp.ones((lo + 2 * cfg.kv_row,)).at[lo:lo + cfg.kv_row].set(
        1.0 / cfg.key_multiplier)
    eng.params = dict(eng.params, layers=[
        dict(lp, attn=dict(lp["attn"], wqkv=lp["attn"]["wqkv"] * scale))
        for lp in eng.params["layers"]])


FAULTS = [_fault_other_slot, _fault_state_not_carried, _fault_tail_advances,
          _fault_conv_tail_at_bucket_end, _fault_group_modulo,
          _fault_no_key_multiplier]


@pytest.mark.parametrize("plant", FAULTS,
                         ids=[f.__name__[7:] for f in FAULTS])
def test_reference_catches_what_the_programs_must_not_do(
        toy, monkeypatch, plant):
    cfg, model, params = toy
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)
    prompts = _prompts(cfg, seed=5)
    eng = _engine(model)
    plant(eng, monkeypatch)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=20))
    # the honest engine reads <= 1e-3 (above)
    assert _worst(params, cfg, prompts, outs) > 0.01


@pytest.mark.parametrize("kw,what", [
    (dict(spec_k=2), "spec_k"), (dict(prefix_cache=True), "prefix_cache")])
def test_no_silent_fallback_for_missing_programs(toy, kw, what):
    _, model, _ = toy
    with pytest.raises(NotImplementedError, match=what):
        _engine(model, **kw)


# -- (e) the paged kernel at five query heads a K/V head ----------------------------

def test_five_query_heads_a_kv_head_through_the_kernel():
    """`paged_attention` with Hq = 5 x Hkv (Falcon-H1's 20 over 4)
    against the dense reference through the same tables."""
    rng = np.random.RandomState(5)
    b, hq, hkv, d, n, bs, maxb = 4, 20, 4, 32, 40, 8, 6
    q = jnp.asarray(rng.randn(b, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(n, bs, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(n, bs, hkv, d), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, n))[:b * maxb]
                         .reshape(b, maxb), jnp.int32)
    lens = jnp.asarray([1, 17, 48, 30], jnp.int32)
    want = pa.paged_attention_reference(q, k, v, tables, lens, sm_scale=0.2)
    got = pa.paged_attention(q, k, v, tables, lens, sm_scale=0.2,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- (f) the cache's dtype per state array -------------------------------------------

def test_each_state_array_is_allocated_and_counted_in_its_own_dtype():
    cache = PagedKVCache(
        2, rows=(64, 64), block_size=4, num_blocks=8, dtype=jnp.bfloat16,
        max_batch=3, slot_state=(("window", (2, 3, 96), None),
                                 ("ssm", (2, 4, 8, 16), "float32")))
    assert [(p.shape, p.dtype) for p in cache.pools] == [
        ((2, 8, 4, 64), jnp.bfloat16), ((2, 8, 4, 64), jnp.bfloat16),
        ((2, 3, 3, 96), jnp.bfloat16), ((2, 3, 4, 8, 16), jnp.float32)]
    assert cmon.stat_get("serve/state/layers") == 4
    assert cmon.stat_get("serve/state/ssm_bytes_per_seq") == 2 * 512 * 4
    assert cmon.stat_get("serve/state/bytes_per_seq") \
        == 2 * 512 * 4 + 2 * 3 * 96 * 2


def test_the_real_cell_keeps_a_16_mib_state_a_sequence():
    """At the published widths (4 layers): 16 MiB of float32 state and
    120 KiB of bf16 convolution tail a sequence; LFM2's tail stays 64 KiB."""
    from types import SimpleNamespace

    from paddle_tpu.text.models import lfm2_moe as lfm

    cfg = fh.FalconH1Config(num_hidden_layers=4, dtype="bfloat16")
    (_, conv, conv_dt), (_, state, state_dt) = \
        fh.FalconH1Model.slot_state.fget(SimpleNamespace(config=cfg))
    assert conv_dt is None and state_dt == "float32"
    assert np.prod(state) * 4 == 16 * 2 ** 20
    assert np.prod(conv) * 2 == 120 * 1024
    lcfg = lfm.Lfm2MoeConfig(num_hidden_layers=10,
                             layer_types=lfm.PUBLISHED_LAYER_TYPES[:10])
    (_, shape, _), = lfm.Lfm2MoeModel.slot_state.fget(
        SimpleNamespace(config=lcfg))
    assert np.prod(shape) * 2 == 64 * 1024


# -- Mellum's programs, pinned ---------------------------------------------------------

# sha256 of the lowered StableHLO text of the state runner's prefill and
# decode for Mellum (attention alone, four cache groups) and of their
# jaxprs with the paged kernel in decode, taken on the parent commit
# (ce7bbef): the runner that now addresses several per-slot arrays and
# offers a recurrence serves a model without either the programs it served
MELLUM_PROGRAMS = {("decode_step", "text"): "d6cfaba0c0500ef3",
                   ("decode_step", "jaxpr"): "2b0cb0a30b4f54b1",
                   ("prefill_step", "text"): "417830575ecf47bf",
                   ("prefill_step", "jaxpr"): "aba239d7fdc6b348"}


@pytest.mark.parametrize("step,form", list(MELLUM_PROGRAMS))
def test_mellum_traces_to_the_programs_it_had(step, form):
    paddle.seed(38)
    model = ml.MellumForCausalLM(ml.MellumConfig(
        initializer_range=0.11, vocab_size=256, hidden_size=64,
        moe_intermediate_size=32, num_hidden_layers=8,
        layer_types=ml.PUBLISHED_LAYER_TYPES[:8], num_attention_heads=8,
        num_key_value_heads=2, head_dim=16, num_experts=8,
        num_experts_per_tok=2, sliding_window=8, max_position_embeddings=128,
        yarn_original_max_position_embeddings=16, yarn_factor=4.0,
        qk_norm_init=2.0))
    model.eval()
    runner = mr.runner_for(model)
    i32 = jnp.int32
    pools = (jnp.zeros((2, 16, 4, 32)), jnp.zeros((2, 16, 4, 32)))
    args = {
        "decode_step": (
            runner.params, jnp.zeros((4,), i32), jnp.zeros((4,), i32), pools,
            jnp.zeros((4, 4, 8), i32), jnp.ones((4,), i32), jnp.zeros((4,)),
            jnp.zeros((4,), i32), jnp.zeros((4,), jnp.uint32)),
        "prefill_step": (
            runner.params, jnp.zeros((1, 16), i32), jnp.int32(5), pools,
            jnp.zeros((4, 8), i32), jnp.float32(0), jnp.int32(0),
            jnp.uint32(0))}[step]
    if form == "text":
        text = jax.jit(functools.partial(getattr(runner, step), block_size=4),
                       donate_argnums=(3,)).lower(*args).as_text()
    else:
        kernel = {"use_kernel": True, "interpret": True} \
            if step == "decode_step" else {}
        text = str(jax.make_jaxpr(functools.partial(
            getattr(runner, step), block_size=4, **kernel))(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == MELLUM_PROGRAMS[step, form]
