"""ISSUE 27: GLM-4.7-Flash (`glm4_moe_lite`) — model, dropless expert
layer, latent pool, runner and engine against the plain float32
reference of the benchmark (`tpubench/models/glm4_moe_lite.py`), at
toy widths with seeded weights on the CPU.

What is compared with what: (a) the model's own full forward with
`reference_logits`, logits to 1e-4; (b) `LLMEngine` (prefill, then
decode through the latent pool in the absorbed form) with the
reference by `teacher_forced_deficits` — the programs return tokens,
not logits, so every emitted token's reference logit has to be the
row's largest to 1e-4 — and token for token with a full re-forward;
(c) absorbed with expanded attention on one layer; (d) the expert
layer with a per-token loop.
"""
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.inference.serving import (LLMEngine, PagedKVCache,
                                          SamplingParams)
from paddle_tpu.inference.serving import state_runner
from paddle_tpu.inference.serving import model_runner as mr
from paddle_tpu.inference.serving.kv_cache import bytes_per_block
from paddle_tpu.text.models import glm4_moe_lite as glm
from paddle_tpu.text.models import mla
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from tpubench.models import glm4_moe_lite as fam  # noqa: E402

TOY = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
           num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
           qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
           intermediate_size=128, moe_intermediate_size=32,
           n_routed_experts=8, num_experts_per_tok=2,
           max_position_embeddings=128)
LIMITS = {"logit_margin": 1e-3, "logit_mean_margin": 1e-4}


@pytest.fixture(scope="module")
def toy():
    cfg = glm.Glm4MoeLiteConfig(**TOY)
    paddle.seed(27)
    model = glm.Glm4MoeLiteForCausalLM(cfg)
    model.eval()
    params = jax.tree_util.tree_map(lambda p: p._value,
                                    model.model._params_tree())
    return cfg, model, params


def _engine(model, **kw):
    return LLMEngine(model, max_batch=4, block_size=4, num_blocks=64,
                     max_seq_len=64, **kw)


# -- (a) model against reference ------------------------------------------------

def test_reference_equals_the_models_full_forward(toy):
    cfg, model, params = toy
    assert float(jnp.abs(params["moe"]["router_b"]).min()) > 0
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


# -- (b) engine against reference ------------------------------------------------

def test_engine_prefill_then_decode_against_the_reference(toy):
    cfg, model, params = toy
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(1, cfg.vocab_size, n))
               for n in (5, 9, 14, 7)]
    eng = _engine(model)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=26))
    assert [len(o) for o in outs] == [26] * 4      # 1 prefill + 25 decode
    for prompt, out in zip(prompts, outs):
        d = fam.teacher_forced_deficits(
            eng.params, cfg.num_attention_heads, prompt, out, 64,
            cfg=cfg, limits=LIMITS, row_bucket=32)
        # 26 tokens' deficits and the request's mean on their scale
        assert d.shape == (27,) and float(d.max()) <= 1e-4, d
    # and token for token the greedy choice of a full re-forward
    seq = list(prompts[1])
    for tok in outs[1][:8]:
        logits = model(paddle.to_tensor(np.asarray([seq]))).numpy()[0, -1]
        assert int(logits.argmax()) == tok
        seq.append(tok)
    assert eng.check_drained() == {}


def test_reference_catches_a_wrong_position(toy):
    """The deficit of a token emitted for ANOTHER context is far
    above the margin: the check can fail."""
    cfg, model, params = toy
    rng = np.random.RandomState(2)
    prompt = list(rng.randint(1, cfg.vocab_size, 9))
    out = _engine(model).generate(
        [prompt], SamplingParams(max_new_tokens=12))[0]
    shifted = prompt[1:] + prompt[:1]
    d = fam.teacher_forced_deficits(params, 4, shifted, out, 32, cfg=cfg,
                                    limits=LIMITS, row_bucket=16)
    assert float(d[:-1].max()) > 0.05
    # the last entry is the mean on the per-token limit's scale
    assert d[-1] == pytest.approx(d[:-1].mean() * 10, rel=1e-5)


# -- (c) absorbed equals expanded attention ---------------------------------------

def test_absorbed_attention_equals_expanded(toy):
    cfg, _, params = toy
    ap = jax.tree_util.tree_map(lambda a: a[1], params["moe"]["attn"])
    s = 11
    u = jnp.asarray(np.random.RandomState(3).randn(s, cfg.hidden_size),
                    jnp.float32)
    pos = jnp.arange(s)
    q_nope, q_rope = mla.mla_query(u, ap, cfg, pos)
    latent = mla.mla_latent(u, ap, cfg, pos)
    assert latent.shape == (s, cfg.latent_row)
    dense = mla.mla_attend_dense(q_nope, q_rope, latent, ap, cfg)
    # every position as one decode query over the rows before it,
    # the rows stored wider than they are and a garbage tail masked
    ctx = jnp.pad(latent, ((0, 5), (0, 128 - cfg.latent_row)),
                  constant_values=0.0).at[s:, :cfg.latent_row].set(7.0)
    ctx = jnp.broadcast_to(ctx, (s,) + ctx.shape)
    absorbed = mla.mla_attend_absorbed(q_nope, q_rope, ctx, pos + 1, ap,
                                       cfg)
    np.testing.assert_allclose(absorbed, dense, atol=2e-5)


# -- (d) the dropless expert layer --------------------------------------------------

def _expert_rig(tokens=48, hidden=16, width=8, experts=6, seed=4):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    return (f(tokens, hidden), f(hidden, experts), f(experts) * 0.2,
            f(experts, hidden, 2 * width), f(experts, width, hidden))


def _per_token_loop(u, idx, weights, w13, w2):
    out = np.zeros(u.shape, np.float32)
    half = w13.shape[-1] // 2
    for t in range(u.shape[0]):
        for e, w in zip(np.asarray(idx[t]), np.asarray(weights[t])):
            g = np.asarray(u[t] @ w13[e])
            act = g[:half] / (1 + np.exp(-g[:half])) * g[half:]
            out[t] += w * (act @ np.asarray(w2[e]))
    return out


def test_expert_layer_drops_nothing_under_skew():
    u, router_w, bias, w13, w2 = _expert_rig()
    # a router so skewed that expert 2 is among every token's choices
    bias = bias.at[2].set(5.0)
    idx, weights = dropless.sigmoid_topk_route(u, router_w, bias, 2, 1.8)
    counts = np.asarray(dropless.expert_counts(idx, 6))
    # it takes all 48 tokens, where an even share is 16 and a GShard
    # capacity of 1.25 x that would have dropped 28
    assert counts[2] == u.shape[0] == 48
    assert counts.sum() == 2 * u.shape[0]
    out = dropless.dropless_expert_ffn(u, idx, weights, w13, w2)
    np.testing.assert_allclose(out, _per_token_loop(u, idx, weights, w13,
                                                    w2), atol=2e-5)
    # as layer 1 of a stack of three, read in place
    stack13 = jnp.stack([w13 * 0 + 9.0, w13, w13 * 0 - 9.0])
    stack2 = jnp.stack([w2 * 0 + 9.0, w2, w2 * 0 - 9.0])
    stacked = jax.jit(dropless.dropless_expert_ffn)(
        u, idx, weights, stack13, stack2, jnp.int32(1))
    np.testing.assert_allclose(stacked, out, atol=1e-6)
    # rows that are not live are left out of the counts
    live = jnp.arange(u.shape[0]) < 10
    assert int(dropless.expert_counts(idx, 6, live).sum()) == 20


def test_selection_bias_chooses_and_does_not_weigh():
    u, router_w, bias, _, _ = _expert_rig()
    scores = np.asarray(jax.nn.sigmoid(u @ router_w))
    idx0, w0 = dropless.sigmoid_topk_route(u, router_w, bias * 0, 2, 1.8)
    idx1, w1 = dropless.sigmoid_topk_route(u, router_w, bias * 0 + jnp.asarray(
        [0, 0, 0, 0, 0, 3.0]), 2, 1.8)
    assert (np.asarray(idx0) != np.asarray(idx1)).any()     # the choice moved
    assert (np.asarray(idx1) == 5).any(axis=-1).all()
    for idx, w in ((idx0, w0), (idx1, w1)):
        s = np.take_along_axis(scores, np.asarray(idx), -1)
        np.testing.assert_allclose(
            w, 1.8 * s / s.sum(-1, keepdims=True), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w).sum(-1), 1.8, rtol=1e-5)


# -- (e) the latent pool ---------------------------------------------------------------

def test_latent_pool_is_one_pool_with_the_same_tables(toy):
    cfg, model, _ = toy
    eng = _engine(model)
    cache = eng.cache
    assert isinstance(eng.runner, state_runner.StateRunner)
    # 40 values a token a layer, stored in whole 128-lane rows
    assert cfg.latent_row == 40 and cache.rows == (128,)
    assert len(cache.pools) == 1
    assert cache.pools[0].shape == (3, 64, 4, 128)
    assert bytes_per_block(3, 4, dtype=np.float32, rows=(128,)) \
        == 3 * 4 * 128 * 4
    from paddle_tpu.core.monitor import stat_get
    assert stat_get("serve/kv/row_values") == 128
    assert stat_get("serve/kv/bytes_per_token") == 3 * 128 * 4
    # a keys-and-values cache of the same geometry: same allocator
    # and tables, two pools
    kv = PagedKVCache(3, 4, 16, block_size=4, num_blocks=64)
    assert len(kv.pools) == 2 and kv.k.shape == (3, 64, 4, 64)
    rid = eng.add_request(list(range(1, 11)),
                          SamplingParams(max_new_tokens=3))
    eng.step()
    # 10 + 1 tokens, and room for the next step's (prepared ahead)
    assert len(cache.allocator.owned(rid)) == 4
    kv.allocator.alloc("r", 4)
    assert list(cache.block_table(rid, 16)) == list(kv.block_table("r", 16))
    while eng.has_unfinished():
        eng.step()
    assert eng.check_drained() == {}
    assert cache.allocator.used_blocks == 0


def test_routing_counters_count_live_tokens_only(toy):
    from paddle_tpu.core.monitor import stat_get

    cfg, model, _ = toy
    names = ["serve/moe/" + n for n in ("assignments", "experts_hit",
                                        "layer_steps", "max_load")]
    before = [stat_get(n) for n in names]
    eng = _engine(model)
    eng.generate([[3, 4, 5, 6, 7]], SamplingParams(max_new_tokens=4))
    a, hit, steps, load = (stat_get(n) - b for n, b in zip(names, before))
    # one prefill of 5 tokens and 3 decode dispatches of 1 live slot
    # (3 idle slots ride along uncounted), 2 expert layers, top-2
    assert steps == 2 * 4
    assert a == 2 * 2 * (5 + 3)
    assert 0 < hit <= a and 0 < load <= a


# -- (f) GPT-2 through the runner ------------------------------------------------------------

def test_gpt2_runner_hands_over_the_same_programs():
    paddle.seed(5)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
        ffn_hidden=64, max_seq_len=32, dropout=0.0))
    model.eval()
    runner = mr.runner_for(model)
    assert isinstance(runner, mr.GPT2Runner)
    assert runner.pool_rows == (32, 32)
    params = runner.params
    pool = jnp.zeros((2, 8, 4, 32))
    args = (jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32))
    rest = (jnp.zeros((4, 8), jnp.int32), jnp.ones((4,), jnp.int32),
            jnp.zeros((4,)), jnp.zeros((4,), jnp.int32),
            jnp.zeros((4,), jnp.uint32))
    old = jax.jit(functools.partial(
        mr.decode_step, n_head=2, eps=1e-5, block_size=4),
        donate_argnums=(3, 4)).lower(params, *args, pool, pool, *rest)
    new = jax.jit(functools.partial(runner.decode_step, block_size=4),
                  donate_argnums=(3,)).lower(
                      params, *args, (pool, pool), *rest)
    # the same StableHLO but for the results' names (the pools are
    # "result[1][0]", "result[1][1]" now, not "result[1]", "result[2]")
    names = re.compile(r'jax\.result_info = "[^"]*"')
    assert names.sub("", old.as_text()) == names.sub("", new.as_text())
    eng = LLMEngine(model, max_batch=2, block_size=4, num_blocks=16)
    assert eng.cache.rows == (32, 32) and eng.max_seq_len == 32
    out, = eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=5))
    seq = [1, 2, 3]
    for tok in out:
        logits = model(paddle.to_tensor(np.asarray([seq]))).numpy()[0, -1]
        assert int(logits.argmax()) == tok
        seq.append(tok)


# -- (g) what the runner does not have for this model ----------------------------------------

@pytest.mark.parametrize("kw,what", [
    (dict(spec_k=2), "spec_k"), (dict(prefix_cache=True), "prefix_cache")])
def test_no_silent_fallback_for_missing_programs(toy, kw, what):
    _, model, _ = toy
    with pytest.raises(NotImplementedError, match=what):
        eng = _engine(model, **kw)
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))


def test_decode_through_the_latent_kernel_emits_the_dense_tokens(
        toy, monkeypatch):
    """ISSUE 33: under the interpreter the engine takes the Pallas
    latent kernel by itself, emits the dense engine's tokens and
    counts every decode dispatch as paged; `use_kernel=False` stays
    the dense path."""
    from paddle_tpu.core.monitor import stat_get

    _, model, _ = toy
    prompts = [[3, 4, 5, 6, 7], [9, 8], list(range(1, 12))]
    names = ("serve/attn/steps", "serve/attn/steps_paged",
             "serve/moe/layer_steps", "serve/moe/layer_steps_kernel")

    def run(**kw):
        before = [stat_get(n) for n in names]
        eng = _engine(model, **kw)
        out = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        return eng, out, [stat_get(n) - b for n, b in zip(names, before)]

    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)
    dense, want, (steps, paged, layer_steps, in_kernel) = run()
    assert not dense.use_kernel and steps == 5 and paged == 0
    # ISSUE 35: three prefills and five decode dispatches through
    # the expert layers, none of them in the grouped-matmul kernel
    assert layer_steps == 8 * dense.runner.params["moe"]["w2"].shape[0]
    assert in_kernel == 0
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    eng, got, counts = run()
    assert eng.use_kernel and eng._kernel_interpret
    assert got == want and counts == [5, 5, layer_steps, layer_steps]
    # the experts' kernel is the traced program's own choice
    eng, got, counts = run(use_kernel=False)
    assert not eng.use_kernel and got == want
    assert counts == [5, 0, layer_steps, layer_steps]


def test_serving_max_seq_len_is_the_deployments(toy):
    cfg, model, _ = toy
    eng = _engine(model)
    assert cfg.max_seq_len == 128 and eng.max_seq_len == 64
    assert eng.max_blocks_per_seq == 16
    assert LLMEngine(model, max_batch=2, block_size=4, num_blocks=64,
                     max_seq_len=10 ** 6).max_seq_len == 128
    out, = eng.generate([list(range(1, 61))],
                        SamplingParams(max_new_tokens=50))
    assert len(out) == 4            # stopped at 64 positions


def test_inputs_prepared_ahead_are_the_inputs_built_afresh(toy):
    """The next step's dispatch inputs, made while a step is in
    flight, equal what the step would build itself; a batch that
    changed in between builds them anew."""
    _, model, _ = toy
    eng = _engine(model)
    rids = [eng.add_request(list(range(1, n)),
                            SamplingParams(max_new_tokens=30))
            for n in (6, 11, 9)]
    for _ in range(3):
        eng.step()
    assert eng._ahead is not None
    kept = eng._ahead
    got = eng._next_arrays()                 # consumes what was kept
    fresh = eng._batch_arrays()
    assert eng._ahead is None and len(got) == len(fresh) == 7
    for a, b in zip(got, fresh):
        np.testing.assert_array_equal(np.asarray(a), b)
    eng._ahead = kept
    eng.abort_request(rids[1])               # the batch changes
    fresh = eng._batch_arrays()
    for a, b in zip(eng._next_arrays(), fresh):
        assert isinstance(a, np.ndarray)     # built anew, not the kept ones
        np.testing.assert_array_equal(a, b)
    while eng.has_unfinished():
        eng.step()
    assert eng.check_drained() == {}
