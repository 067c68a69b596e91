"""ISSUE 34: LFM2-24B-A2B (`lfm2_moe`) — gated short convolutions whose
state lives in per-slot arrays beside the paged keys and values,
grouped K/V heads through the paged kernel, the state runner and the
engine against the plain float32 reference of the benchmark
(`tpubench/models/lfm2_moe.py`), at toy widths with seeded weights on
the CPU.

What is compared with what: (a) the model's own full forward with
`reference_logits`, logits to 1e-4; (b) `LLMEngine` (prefill told its
slot, then decode through the pools and the slot state) with the
reference by `teacher_forced_deficits` — the programs return tokens,
not logits, so every emitted token's reference logit has to be the
row's largest to 1e-4 — with prompts shorter than their bucket and a
prompt of one token, after a slot's reuse, an eviction and a pool
rebuild; (c) the same check reading planted faults; (d) the paged
kernel with grouped heads against the dense path; (e) the three
accepted runners' programs and the kernel's own, pinned by digest.
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
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.incubate.nn.pallas import paged_attention as pa
from paddle_tpu.inference.serving import LLMEngine, SamplingParams
from paddle_tpu.inference.serving import model_runner as mr
from paddle_tpu.inference.serving import state_runner
from paddle_tpu.monitor import chaos
from paddle_tpu.text.models import glm4_moe_lite as glm
from paddle_tpu.text.models import lfm2_moe as lfm
from paddle_tpu.text.models import longcat_flash as lc
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from tpubench.models import lfm2_moe as fam  # noqa: E402

# 7 layers: conv conv attn conv conv conv attn; 2 dense FFNs, 5 expert
# layers; 8 query heads over 2 K/V heads (Hq = 4 x Hkv, as published).
# The matrices' scale keeps the published gain a matmul (0.02 x 2048^0.5
# = 0.9 = 0.11 x 64^0.5): at 0.02 a toy's layers add nothing to the
# embedding and, the head being tied, it echoes its input
TOY = dict(initializer_range=0.11,
           vocab_size=256, hidden_size=64, intermediate_size=96,
           moe_intermediate_size=32, num_hidden_layers=7,
           layer_types=lfm.PUBLISHED_LAYER_TYPES[:7],
           num_attention_heads=8, num_key_value_heads=2, num_experts=8,
           num_experts_per_tok=2, max_position_embeddings=128)
LIMITS = {"logit_margin": 1e-3, "logit_mean_margin": 1e-4}
PROMPT_LENS = (5, 9, 1, 7, 14, 2)     # block 4: none fills its bucket but 1


@pytest.fixture(scope="module")
def toy():
    cfg = lfm.Lfm2MoeConfig(**TOY)
    paddle.seed(34)
    model = lfm.Lfm2MoeForCausalLM(cfg)
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
    assert cfg.count("conv") == 5 and cfg.count("full_attention") == 2
    assert "head" not in params                       # tied
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 16))
    out = model(paddle.to_tensor(ids)).numpy()
    for row, got in zip(ids, out):
        ref = np.asarray(fam.reference_logits(
            params, jnp.asarray(row), cfg, q_block=8))
        np.testing.assert_allclose(got, ref, atol=1e-4)
    # the window of rows that meets the head is the full result's
    part = np.asarray(fam.reference_logits(
        params, jnp.asarray(ids[0]), cfg, start=5, n_rows=4, q_block=8))
    np.testing.assert_allclose(part, out[0, 5:9], atol=1e-4)


def test_an_untied_head_is_a_leaf_of_its_own():
    cfg = lfm.Lfm2MoeConfig(**{**TOY, "tie_word_embeddings": False})
    paddle.seed(3)
    model = lfm.Lfm2MoeForCausalLM(cfg)
    params = jax.tree_util.tree_map(lambda p: p._value,
                                    model.model._params_tree())
    assert params["head"].shape == (64, 256)
    ids = np.random.RandomState(0).randint(0, 256, (1, 8))
    ref = np.asarray(fam.reference_logits(params, jnp.asarray(ids[0]), cfg))
    np.testing.assert_allclose(model(paddle.to_tensor(ids)).numpy()[0], ref,
                               atol=1e-4)


def test_what_the_config_refuses():
    for bad in (dict(conv_bias=True), dict(norm_topk_prob=False),
                dict(num_key_value_heads=3), dict(num_hidden_layers=6)):
        with pytest.raises(ValueError):
            lfm.Lfm2MoeConfig(**{**TOY, **bad})


# -- (b) engine against reference ------------------------------------------------

def test_engine_prefill_then_decode_against_the_reference(toy):
    """Six requests through four slots (two slots are used twice),
    prompts shorter than their bucket and one of a single token,
    whose state is zeros."""
    cfg, model, params = toy
    prompts = _prompts(cfg)
    before = cmon.stat_get("serve/state/slot_writes")
    eng = _engine(model)
    assert isinstance(eng.runner, state_runner.StateRunner)
    assert [p.shape for p in eng.cache.pools] == [
        (2, 64, 4, 16), (2, 64, 4, 16), (5, 4, 2, 64)]
    assert eng.cache.n_paged == 2
    assert cmon.stat_get("serve/kv/bytes_per_token") == 2 * 2 * 16 * 4
    assert cmon.stat_get("serve/state/layers") == 5
    assert cmon.stat_get("serve/state/bytes_per_seq") == 5 * 2 * 64 * 4
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=20))
    assert [len(o) for o in outs] == [20] * 6
    assert cmon.stat_get("serve/state/slot_writes") == before + 6
    assert _worst(params, cfg, prompts, outs) <= 1e-4
    # and token for token the greedy choice of a full re-forward
    seq = list(prompts[2])
    for tok in outs[2][:8]:
        logits = model(paddle.to_tensor(np.asarray([seq]))).numpy()[0, -1]
        assert int(logits.argmax()) == tok
        seq.append(tok)
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
    the engine rebuilds the paged pools AND the slot state and replays
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
    assert all(p.shape[1] == (4 if i >= 2 else 64) and not p.is_deleted()
               for i, p in enumerate(eng.cache.pools))


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
    assert _worst(params, cfg, prompts, outs) <= 1e-4
    assert eng.check_drained() == {}


# -- (b2) the next dispatch handed over before the last one's tokens ------------

def _steps_run_ahead(eng):
    """Drive the engine to the end; the steps that found a dispatch
    already in flight."""
    n = 0
    while eng.has_unfinished():
        n += eng._inflight is not None
        eng.step()
    return n


@pytest.mark.parametrize("case", ["full", "off", "not_full", "stop_token",
                                  "abort", "oom"])
def test_a_full_batch_decodes_a_step_ahead_of_the_host(toy, case):
    """With the batch full, every request ending by length and the
    inputs prepared ahead, an engine made with `run_ahead=True` hands
    the device the next decode dispatch, fed the tokens where they
    are, before it fetches the last one's: the slot state and the K/V
    rows move a token at a time all the same, and the tokens are
    those of an engine that never does so (a batch with a free slot).
    Not unless asked to, not with a free slot, not where a token's
    value may end a request; a request aborted with its token in
    flight gets none and the others lose none; an out-of-memory at
    the hand-over evicts as ever and the tokens stay exact; no
    dispatch is wasted at the end."""
    cfg, model, params = toy
    prompts = _prompts(cfg)[:4]
    sp = SamplingParams(max_new_tokens=14)
    want = _engine(model, max_batch=6).generate(prompts, sp)
    before = cmon.stat_get("serve/attn/steps")
    if case == "not_full":
        eng = _engine(model, max_batch=5, run_ahead=True)
        rids = [eng.add_request(p, sp) for p in prompts]
        assert _steps_run_ahead(eng) == 0
    elif case == "off":
        eng = _engine(model)
        rids = [eng.add_request(p, sp) for p in prompts]
        assert _steps_run_ahead(eng) == 0
    elif case == "stop_token":
        eng = _engine(model, run_ahead=True)
        rids = [eng.add_request(p, sp) for p in prompts[:3]]
        rids.append(eng.add_request(prompts[3], SamplingParams(
            max_new_tokens=14, stop_token_ids=(cfg.vocab_size - 1,))))
        assert _steps_run_ahead(eng) == 0
    else:
        eng = _engine(model, run_ahead=True)
        rids = [eng.add_request(p, sp) for p in prompts]
        eng.step()                       # four prefills + a decode
        assert eng._inflight is None     # ... which compiled
        eng.step()
        assert eng._inflight is not None
        if case == "abort":
            eng.abort_request(rids[1])   # its next token is in flight
            n_aborted = len(eng.get_request(rids[1]).output_ids)
        if case == "oom":
            with chaos.inject("serve_decode", "resource_exhausted",
                              times=1):
                eng.step()               # the hand-over fails: evict
            assert cmon.stat_get("serve/oom_evictions") >= 1
        assert _steps_run_ahead(eng) >= (1 if case == "abort" else 8)
    outs = [eng.get_request(r).output_ids for r in rids]
    if case == "abort":
        assert len(outs[1]) == n_aborted and outs[1] == want[1][:n_aborted]
        outs[1] = want[1]
    assert outs == want
    if case == "full":
        # 13 decode dispatches for 13 tokens after the prefill's: the
        # step before the last hands nothing over
        assert cmon.stat_get("serve/attn/steps") == before + 13
    assert _worst(params, cfg, prompts, want) <= 1e-4
    assert eng._inflight is None and eng.check_drained() == {}


@pytest.mark.parametrize("kw,what", [
    (dict(spec_k=2), "spec_k"), (dict(prefix_cache=True), "prefix_cache")])
def test_no_silent_fallback_for_missing_programs(toy, kw, what):
    _, model, _ = toy
    with pytest.raises(NotImplementedError, match=what):
        _engine(model, **kw)


# -- (c) the check reads planted faults ---------------------------------------------

def _fault_bucket_end(eng, mp):
    mp.setattr(state_runner, "_window_tail",
               lambda zp, prompt_len, n: zp[zp.shape[0] - n:])


def _fault_other_slot(eng, mp):
    real = eng.runner.prefill_step
    eng.runner.prefill_step = lambda *a, **kw: real(
        *a[:-1], (a[-1] + 1) % eng.max_batch, **kw)


def _mapped(eng, fn):
    eng.params = dict(eng.params, layers=[
        {k: fn(k, v) for k, v in lp.items()} for lp in eng.params["layers"]])


def _fault_taps_reversed(eng, mp):
    _mapped(eng, lambda k, v: dict(v, taps=v["taps"][::-1])
            if k == "conv" else v)


def _fault_kv_head_modulo(eng, mp):
    """Query head h reading K/V head h % Hkv, planted as the
    permutation of the query heads that makes it so."""
    hq, hkv, d = 8, 2, 8
    src = np.asarray([h // (hq // hkv) + hkv * (h % (hq // hkv))
                      for h in range(hq)])
    cols = (src[:, None] * d + np.arange(d)).reshape(-1)

    def permute(k, v):
        if k != "attn":
            return v
        wqkv = v["wqkv"].at[:, :hq * d].set(v["wqkv"][:, cols])
        return dict(v, wqkv=wqkv, wo=v["wo"][cols])

    _mapped(eng, permute)


def _fault_no_qk_norm(eng, mp):
    real = lfm.rms_norm
    mp.setattr(lfm, "rms_norm", lambda x, w, eps:
               x if x.ndim == 3 else real(x, w, eps))


def _fault_blocks_off(eng, mp):
    real = eng.runner.decode_step
    eng.runner.decode_step = lambda p, i, pos, pools, tables, *a, **kw: \
        real(p, i, pos, pools, jnp.roll(tables, 1, axis=1), *a, **kw)


def _fault_not_renormalised(eng, mp):
    def route(u, router_w, bias, top_k, scale):
        scores = jax.nn.sigmoid(u.astype(jnp.float32) @ router_w)
        _, idx = jax.lax.top_k(scores + bias, top_k)
        return idx.astype(jnp.int32), scale * jnp.take_along_axis(
            scores, idx, axis=-1)

    mp.setattr(lfm, "sigmoid_topk_route", route)


def _fault_fp8(eng, mp):
    """Every matrix rounded to fp8 e4m3: a precision below the
    configuration's is told from it."""
    eng.params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, eng.params)


FAULTS = [_fault_bucket_end, _fault_other_slot, _fault_taps_reversed,
          _fault_kv_head_modulo, _fault_no_qk_norm, _fault_blocks_off,
          _fault_not_renormalised, _fault_fp8]


@pytest.mark.parametrize("plant", FAULTS,
                         ids=[f.__name__[7:] for f in FAULTS])
def test_reference_catches_what_the_programs_must_not_do(
        toy, monkeypatch, plant):
    cfg, model, params = toy
    prompts = _prompts(cfg, seed=5)
    eng = _engine(model)
    plant(eng, monkeypatch)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=20))
    # the honest engine reads <= 1e-4 (above); the per-token limit of
    # the toy cell is 1e-3
    assert _worst(params, cfg, prompts, outs) > 0.01


# -- (d) the paged kernel with grouped heads ----------------------------------------

def test_decode_through_the_paged_kernel_emits_the_dense_tokens(
        toy, monkeypatch):
    """Under the interpreter the engine takes the Pallas paged kernel
    by itself (8 query heads over 2 K/V heads), emits the dense
    engine's tokens and counts every decode dispatch as paged."""
    _, model, _ = toy
    prompts = [[3, 4, 5, 6, 7], [9, 8], list(range(1, 12))]
    names = ("serve/attn/steps", "serve/attn/steps_paged",
             "serve/moe/layer_steps", "serve/moe/layer_steps_kernel")

    def run(**kw):
        before = [cmon.stat_get(n) for n in names]
        eng = _engine(model, **kw)
        out = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        return eng, out, [cmon.stat_get(n) - b
                          for n, b in zip(names, before)]

    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)
    dense, want, (steps, paged, layer_steps, in_kernel) = run()
    assert not dense.use_kernel and steps == 5 and paged == 0
    # ISSUE 35: the expert layers of three prefills and five decode
    # dispatches; in the grouped-matmul kernel under the interpreter
    assert layer_steps > 0 and layer_steps % 8 == 0 and in_kernel == 0
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    eng, got, counts = run()
    assert eng.use_kernel and eng._kernel_interpret
    assert got == want and counts == [5, 5, layer_steps, layer_steps]


@pytest.mark.parametrize("hq,hkv,dtype,tol", [
    (8, 2, jnp.float32, 2e-5), (4, 1, jnp.float32, 2e-5),
    (8, 2, jnp.bfloat16, 2e-2), (4, 4, jnp.float32, 2e-5)])
def test_grouped_heads_through_the_kernel_equal_repeated_heads(
        hq, hkv, dtype, tol):
    """`paged_attention` and the verify kernel with Hq = G x Hkv
    against the dense references given the K/V heads repeated."""
    rng = np.random.RandomState(hq + hkv)
    b, d, n, bs, maxb = 5, 32, 40, 8, 7
    q = jnp.asarray(rng.randn(b, hq, d), dtype)
    kp = jnp.asarray(rng.randn(n, bs, hkv, d), dtype)
    vp = jnp.asarray(rng.randn(n, bs, hkv, d), dtype)
    tables = jnp.asarray(
        rng.permutation(n - 1)[:b * maxb].reshape(b, maxb) + 1, jnp.int32)
    lens = jnp.asarray([1, 9, 30, 53, 17], jnp.int32)
    rep = functools.partial(jnp.repeat, repeats=hq // hkv, axis=2)
    want = pa.paged_attention_reference(q, rep(kp), rep(vp), tables, lens,
                                        sm_scale=0.2)
    f32 = lambda a: np.asarray(a, np.float32)        # noqa: E731
    # the dense reference takes grouped pools as they are, bit for bit
    np.testing.assert_array_equal(f32(want), f32(
        pa.paged_attention_reference(q, kp, vp, tables, lens, sm_scale=0.2)))
    got = pa.paged_attention(q, kp, vp, tables, lens, sm_scale=0.2,
                             interpret=True)
    np.testing.assert_allclose(f32(got), f32(want), atol=tol)
    qm = jnp.asarray(rng.randn(b, 3, hq, d), dtype)
    got = pa.paged_attention_multi(qm, kp, vp, tables, lens, sm_scale=0.2,
                                   interpret=True)
    want = pa.paged_attention_multi_reference(qm, rep(kp), rep(vp), tables,
                                              lens, sm_scale=0.2)
    np.testing.assert_allclose(f32(got), f32(want), atol=2 * tol)


def test_the_kernel_is_asked_with_the_kv_heads(monkeypatch):
    """A page is `[BS, Hkv*D]`: the lanes that have to be whole are
    the K/V heads', and the query heads whole groups of them."""
    from paddle_tpu.incubate.nn import pallas

    monkeypatch.setattr(pallas, "_on_tpu", lambda: True)
    monkeypatch.setattr(pallas, "_partitioned", lambda: False)
    assert pa.paged_decode_supported(32, 64, 16, num_kv_heads=8)
    assert not pa.paged_decode_supported(32, 64, 16, num_kv_heads=1)
    assert not pa.paged_decode_supported(32, 64, 16, num_kv_heads=5)
    assert pa.paged_decode_supported(16, 64, 16)
    assert not pa.paged_decode_supported(16, 64, 12)
    with pytest.raises(ValueError, match="under query"):
        pa.paged_attention(jnp.zeros((1, 6, 8)), jnp.zeros((4, 8, 4, 8)),
                           jnp.zeros((4, 8, 4, 8)),
                           jnp.zeros((1, 2), jnp.int32),
                           jnp.ones((1,), jnp.int32), interpret=True)


# -- (e) the programs that were, pinned ----------------------------------------------

# sha256 of `str(jax.make_jaxpr(...))`: Hkv = Hq traces the one kernel,
# with no step of its own for grouped heads (taken on the parent commit,
# dd8802e, and again when the page copies became a rolled loop over a
# group's live pages)
KERNEL_JAXPRS = {(1, "float32"): "8708e472e2d911a1",
                 (1, "bfloat16"): "5e93896385765b77",
                 (3, "float32"): "746675e5688e8db9",
                 (3, "bfloat16"): "d0eddcc47ae62832"}


@pytest.mark.parametrize("t_q,dtype", list(KERNEL_JAXPRS))
def test_equal_heads_trace_the_kernel_that_was(t_q, dtype):
    b, h, d, n, bs, maxb = 4, 4, 32, 24, 8, 5
    q = jnp.zeros((b, t_q, h, d) if t_q > 1 else (b, h, d), dtype)
    pool = jnp.zeros((n, bs, h, d), dtype)
    fn = pa.paged_attention_multi if t_q > 1 else pa.paged_attention
    text = str(jax.make_jaxpr(
        lambda q, k, v, t, l: fn(q, k, v, t, l, sm_scale=0.25))(
            q, pool, pool, jnp.zeros((b, maxb), jnp.int32),
            jnp.ones((b,), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == KERNEL_JAXPRS[t_q, dtype]


def _glm():
    return glm.Glm4MoeLiteForCausalLM(glm.Glm4MoeLiteConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=8, num_experts_per_tok=2,
        max_position_embeddings=128)), 3


def _longcat():
    return lc.LongcatFlashForCausalLM(lc.LongcatFlashConfig(
        vocab_size=256, hidden_size=64, ffn_hidden_size=128,
        expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
        q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=12,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
        zero_expert_num=8, moe_topk=4, max_position_embeddings=128,
        expert_first=4, experts_held=4)), 4


def _gpt2():
    return GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
        ffn_hidden=64, max_seq_len=32, dropout=0.0)), 2


# sha256 of the lowered StableHLO text of each runner's decode and
# prefill step at these toy shapes, taken on the parent commit
PROGRAMS = {(_glm, "decode_step"): "b93d76404dc217bf",
            (_glm, "prefill_step"): "ae14442daf7c41e1",
            (_longcat, "decode_step"): "996f5e367eca87aa",
            (_longcat, "prefill_step"): "b5831bd14719e5d7",
            (_gpt2, "decode_step"): "e3fbd8f5418bd494",
            (_gpt2, "prefill_step"): "aa21803b3c8c036d"}


@pytest.mark.parametrize("build,step", list(PROGRAMS), ids=[
    f"{b.__name__[1:]}-{s}" for b, s in PROGRAMS])
def test_the_accepted_runners_serve_the_programs_they_did(build, step):
    """The engine's new argument (a prefill's slot) and the cache's
    new arrays reach only a runner that declares slot state."""
    paddle.seed(27)
    model, layers = build()
    model.eval()
    runner = mr.runner_for(model)
    assert runner.slot_state == ()
    i32 = jnp.int32
    pools = tuple(jnp.zeros((layers, 16, 4, w)) for w in runner.pool_rows)
    args = {
        "decode_step": (
            runner.params, jnp.zeros((4,), i32), jnp.zeros((4,), i32), pools,
            jnp.zeros((4, 8), i32), jnp.ones((4,), i32), jnp.zeros((4,)),
            jnp.zeros((4,), i32), jnp.zeros((4,), jnp.uint32)),
        "prefill_step": (
            runner.params, jnp.zeros((1, 16), i32), jnp.int32(5), pools,
            jnp.zeros((8,), i32), jnp.float32(0), jnp.int32(0),
            jnp.uint32(0))}[step]
    text = jax.jit(functools.partial(getattr(runner, step), block_size=4),
                   donate_argnums=(3,)).lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PROGRAMS[build, step]


# sha256 of the jaxpr of the latent models' decode step through the paged
# latent kernel (in the interpreter) at the toy shapes above, taken on the
# parent commit (05b7560) before the latent models joined the one runner:
# the path the cells run on the chip, which `PROGRAMS` (the dense gather)
# does not trace
KERNEL_PROGRAMS = {_glm: "4c7703c3960bc1da", _longcat: "13edbf44cdb32b26"}


@pytest.mark.parametrize("build", list(KERNEL_PROGRAMS),
                         ids=[b.__name__[1:] for b in KERNEL_PROGRAMS])
def test_the_latent_models_decode_through_the_kernel_as_they_did(
        build, monkeypatch):
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)
    paddle.seed(27)
    model, layers = build()
    model.eval()
    runner = mr.runner_for(model)
    i32 = jnp.int32
    pools = tuple(jnp.zeros((layers, 16, 4, w)) for w in runner.pool_rows)
    text = str(jax.make_jaxpr(functools.partial(
        runner.decode_step, block_size=4, use_kernel=True, interpret=True))(
            runner.params, jnp.zeros((4,), i32), jnp.zeros((4,), i32), pools,
            jnp.zeros((4, 8), i32), jnp.ones((4,), i32), jnp.zeros((4,)),
            jnp.zeros((4,), i32), jnp.zeros((4,), jnp.uint32)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == KERNEL_PROGRAMS[build]


def test_the_route_is_glms_rule_with_no_shared_expert(toy):
    """`moe_ffn` against a per-token loop over the chosen experts."""
    cfg, _, params = toy
    mp = params["layers"][3]["moe"]
    u = jnp.asarray(np.random.RandomState(8).randn(6, 64), jnp.float32)
    out, counts = lfm.moe_ffn(u, mp, cfg)
    idx, w = dropless.sigmoid_topk_route(u, mp["router_w"], mp["router_b"],
                                         2, 1.0)
    assert int(counts.sum()) == 12
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    for t in range(6):
        want = sum(float(w[t, i]) * np.asarray(lfm.swiglu(
            u[t], mp["w13"][int(idx[t, i])], mp["w2"][int(idx[t, i])]))
            for i in range(2))
        np.testing.assert_allclose(np.asarray(out[t]), want, atol=1e-5)
