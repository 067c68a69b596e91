"""ISSUE 31: LongCat-Flash — shortcut-connected double layers, a
softmax router over real and zero-compute experts, and one chip's
share of a layer's experts — against the plain float32 reference of
the benchmark (`tpubench/models/longcat_flash.py`), at toy widths
with seeded weights on the CPU.

What is compared with what: (a) the model's own full forward with
`reference_logits`, logits to 1e-4, for a chip that holds every
expert and for one that holds a share; (b) `LLMEngine` (prefill,
then decode through the latent pool with two attentions' rows a
layer) with the reference by `teacher_forced_deficits`, and token
for token with a full re-forward; (c) the routing rule; (d) the
shares of a layer add up to the uncut reference's layer; (e) GLM's
programs through the generalised functions are the programs they
were.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.inference.serving import LLMEngine, SamplingParams
from paddle_tpu.inference.serving import state_runner
from paddle_tpu.inference.serving import model_runner as mr
from paddle_tpu.text.models import glm4_moe_lite as glm
from paddle_tpu.text.models import longcat_flash as lc

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from tpubench.models import longcat_flash as fam  # noqa: E402

TOY = dict(vocab_size=256, hidden_size=64, ffn_hidden_size=128,
           expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
           q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=12,
           qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
           zero_expert_num=8, moe_topk=4, max_position_embeddings=128)
SHARE = dict(expert_first=4, experts_held=4)
LIMITS = {"logit_margin": 1e-3, "logit_mean_margin": 1e-4}


def _build(**kw):
    cfg = lc.LongcatFlashConfig(**TOY, **kw)
    paddle.seed(31)
    model = lc.LongcatFlashForCausalLM(cfg)
    model.eval()
    params = jax.tree_util.tree_map(lambda p: p._value,
                                    model.model._params_tree())
    return cfg, model, params


@pytest.fixture(scope="module")
def whole():
    return _build()


@pytest.fixture(scope="module")
def share():
    return _build(**SHARE)


def _engine(model, **kw):
    return LLMEngine(model, max_batch=4, block_size=4, num_blocks=64,
                     max_seq_len=64, **kw)


# -- (a) model against reference ------------------------------------------------

@pytest.mark.parametrize("which", ["whole", "share"])
def test_reference_equals_the_models_full_forward(which, request):
    cfg, model, params = request.getfixturevalue(which)
    assert cfg.mla_q_scale == 2.0 and cfg.mla_kv_scale == 2 ** 0.5
    assert params["layers"]["router_w"].shape == (2, 64, 24)
    assert params["layers"]["w13"].shape[:2] == (2, cfg.experts_held)
    assert float(jnp.abs(params["layers"]["router_b"]).min()) > 0
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

def test_engine_prefill_then_decode_against_the_reference(share):
    cfg, model, params = share
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(1, cfg.vocab_size, n))
               for n in (5, 9, 14, 7)]
    eng = _engine(model)
    assert isinstance(eng.runner, state_runner.StateRunner)
    # two attentions a layer keep rows: the pool's layer count is the
    # runner's, not the config's
    assert eng.runner.pool_layers == 4 and cfg.num_layers == 2
    assert eng.cache.pools[0].shape == (4, 64, 4, 128)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=26))
    assert [len(o) for o in outs] == [26] * 4      # 1 prefill + 25 decode
    for prompt, out in zip(prompts, outs):
        d = fam.teacher_forced_deficits(
            eng.params, cfg.num_attention_heads, prompt, out, 64,
            cfg=cfg, limits=LIMITS, row_bucket=32)
        assert d.shape == (27,) and float(d.max()) <= 1e-4, d
    # and token for token the greedy choice of a full re-forward
    seq = list(prompts[1])
    for tok in outs[1][:8]:
        logits = model(paddle.to_tensor(np.asarray([seq]))).numpy()[0, -1]
        assert int(logits.argmax()) == tok
        seq.append(tok)
    assert eng.check_drained() == {}


def test_reference_catches_what_the_block_must_not_do(share, monkeypatch):
    """A token emitted for another context lies far above the margin,
    and a reference that drops the zero-compute picks is another
    function: the check can fail."""
    cfg, model, params = share
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
    # zero-compute picks dropped in the reference: not the model
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, 16))
    good = fam.reference_logits(params, ids, cfg, q_block=8)
    whole_moe = fam.reference_moe
    monkeypatch.setattr(
        fam, "reference_moe", lambda u, *a, **k:
        whole_moe(u, *a, **k) - _zero_part(u, a[0], a[1], cfg))
    bad = fam.reference_logits(params, ids, cfg, q_block=8)
    assert float(jnp.abs(good - bad).max()) > 0.01


def _zero_part(u, router_w, router_b, cfg):
    chosen, weight = fam.reference_route(u, router_w, router_b, cfg)
    w = jnp.where(chosen >= cfg.n_routed_experts, weight, 0.0)
    return w.sum(-1, keepdims=True) * u


# -- (c) the routing rule ---------------------------------------------------------

def _rig(tokens=40, hidden=16, outputs=12, seed=4):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s) * 0.5, jnp.float32)  # noqa: E731
    return f(tokens, hidden), f(hidden, outputs), f(outputs) * 0.05


def test_softmax_route_chooses_by_bias_and_weighs_by_score():
    u, router_w, bias = _rig()
    scores = np.asarray(jax.nn.softmax(u @ router_w, axis=-1))
    idx0, w0 = dropless.softmax_topk_route(u, router_w, bias * 0, 3, 6.0)
    idx1, w1 = dropless.softmax_topk_route(
        u, router_w, bias * 0 + jnp.zeros(12).at[11].set(3.0), 3, 6.0)
    assert (np.asarray(idx0) != np.asarray(idx1)).any()   # the choice moved
    assert (np.asarray(idx1) == 11).any(axis=-1).all()
    for idx, w in ((idx0, w0), (idx1, w1)):
        # scale 6 x the score as it is: the bias is not in it, and
        # the three weights do not sum to anything fixed
        np.testing.assert_allclose(
            w, 6.0 * np.take_along_axis(scores, np.asarray(idx), -1),
            rtol=1e-5)
    assert np.ptp(np.asarray(w0).sum(-1)) > 0.1
    # the reference's rule is the same rule
    cfg = lc.LongcatFlashConfig(**{**TOY, "moe_topk": 3,
                                   "n_routed_experts": 8,
                                   "zero_expert_num": 4})
    chosen, weight = fam.reference_route(u, router_w, bias, cfg)
    idx, w = dropless.softmax_topk_route(u, router_w, bias, 3, 6.0)
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(idx, -1))
    np.testing.assert_allclose(np.sort(weight, -1), np.sort(w, -1),
                               rtol=1e-5)


def test_identity_picks_add_the_weighted_token():
    u, router_w, bias = _rig()
    idx, w = dropless.softmax_topk_route(u, router_w, bias, 3, 6.0)
    got = np.asarray(dropless.identity_expert_sum(u, idx, w, 8))
    want = np.zeros(u.shape, np.float32)
    for t in range(u.shape[0]):
        for e, we in zip(np.asarray(idx[t]), np.asarray(w[t])):
            if e >= 8:
                want[t] += we * np.asarray(u[t])
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_decode_through_the_latent_kernel_emits_the_dense_tokens(
        share, monkeypatch):
    """ISSUE 33: the same decode program serves this model's two
    attentions a layer through the Pallas latent kernel (4 attentions'
    rows in one pool, the tables shifted to each): under the
    interpreter the engine emits the dense engine's tokens and counts
    every decode dispatch as paged."""
    from paddle_tpu.core.monitor import stat_get

    _, model, _ = share
    prompts = [[3, 4, 5, 6, 7], list(range(1, 12))]
    names = ("serve/attn/steps", "serve/attn/steps_paged",
             "serve/moe/layer_steps", "serve/moe/layer_steps_kernel")

    def run():
        before = [stat_get(n) for n in names]
        eng = _engine(model)
        out = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        return eng, out, [stat_get(n) - b for n, b in zip(names, before)]

    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)
    dense, want, counts = run()
    # two prefills and five decode dispatches of two double layers;
    # ISSUE 35: their groups in the kernel only under the interpreter
    assert not dense.use_kernel and counts == [5, 0, 14, 0]
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    eng, got, counts = run()
    assert eng.use_kernel and eng.cache.pools[0].shape[0] == 4
    assert got == want and counts == [5, 5, 14, 14]


# -- (d) the shares add up to the layer ----------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(whole):
    """Four chips hold 4 experts each of a layer's 16 (+ 8
    zero-compute outputs): each routes over all 24 and computes its
    own experts' part; the four parts, with the zero-compute part
    that every chip computes alike counted once, are the uncut
    reference's layer. A pick of an expert held elsewhere adds
    nothing: one share alone is not the layer."""
    cfg, _, params = whole
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"])
    u = jnp.asarray(np.random.RandomState(5).randn(24, cfg.hidden_size),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = fam.reference_moe(u, lp["router_w"], lp["router_b"],
                                  lp["w13"], lp["w2"], cfg)
    zero = _zero_part(u, lp["router_w"], lp["router_b"], cfg)
    total, counts = jnp.zeros_like(u), []
    for first in (0, 4, 8, 12):
        c = lc.LongcatFlashConfig(**TOY, expert_first=first, experts_held=4)
        mine = {**lp, "w13": lp["w13"][first:first + 4],
                "w2": lp["w2"][first:first + 4]}
        part, stats = lc.scmoe_ffn(u, mine, c)
        with jax.default_matmul_precision("highest"):
            ref = fam.reference_moe(u, lp["router_w"], lp["router_b"],
                                    mine["w13"], mine["w2"], cfg, first)
        np.testing.assert_allclose(part, ref, atol=2e-5)
        assert float(jnp.abs(part - uncut).max()) > 1e-3
        total = total + part - zero
        counts.append(stats)
    np.testing.assert_allclose(total + zero, uncut, atol=5e-5)
    # every pick is counted once: held somewhere, or zero-compute
    picks = np.asarray(counts[0]["moe_picks"])
    assert picks[0] == 24 * cfg.moe_topk
    assert all((np.asarray(c["moe_picks"]) == picks).all() for c in counts)
    held = sum(int(c["moe_counts"].sum()) for c in counts)
    assert held + picks[1] == picks[0] and 0 < picks[1] < picks[0]


def test_a_share_of_a_stack_is_read_in_place():
    """`first` with `layer`: layer 1 of three, experts 2..5 of 6."""
    rng = np.random.RandomState(6)
    f = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    u, w13, w2 = f(20, 16), f(3, 6, 16, 8), f(3, 6, 4, 16)
    idx = jnp.asarray(rng.randint(0, 9, (20, 3)), jnp.int32)
    w = jnp.abs(f(20, 3))
    got = jax.jit(functools.partial(dropless.dropless_expert_ffn, first=2))(
        u, idx, w, w13[:, 2:6], w2[:, 2:6], jnp.int32(1))
    held = (idx >= 2) & (idx < 6)
    want = dropless.dropless_expert_ffn(
        u, jnp.where(held, idx, 0), jnp.where(held, w, 0.0), w13[1], w2[1])
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- (e) the counters, and GLM as it was ----------------------------------------------

def test_routing_counters_tell_choices_from_assignments(share):
    from paddle_tpu.core.monitor import stat_get

    cfg, model, _ = share
    names = ["serve/moe/" + n for n in (
        "choices", "zero_choices", "assignments", "experts_hit",
        "layer_steps", "max_load")]
    before = [stat_get(n) for n in names]
    _engine(model).generate([[3, 4, 5, 6, 7]],
                            SamplingParams(max_new_tokens=4))
    c, z, a, hit, steps, load = (stat_get(n) - b
                                 for n, b in zip(names, before))
    # one prefill of 5 tokens and 3 decode dispatches of 1 live slot,
    # 2 double layers, top-4 of 24 outputs of which 4 are held here
    assert steps == 2 * 4 and c == 2 * 4 * (5 + 3)
    assert 0 < z < c and 0 <= a <= c - z
    assert hit <= a and load <= a


def _old_dropless_expert_ffn(u, idx, weights, w13, w2, layer=None):
    """`dropless_expert_ffn` as it was before it could be told which
    experts it holds (PR 27-30), for the comparison below."""
    t, k = idx.shape
    n_experts = w13.shape[-3]
    flat = idx.reshape(t * k)
    order = jnp.argsort(flat, stable=True)
    rows = jnp.take(u, order // k, axis=0)
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    if layer is not None:
        groups = w13.shape[0] * n_experts
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((groups,), jnp.int32), sizes,
            (layer * n_experts,))
        w13 = w13.reshape((groups,) + w13.shape[2:])
        w2 = w2.reshape((groups,) + w2.shape[2:])
    gate, up = jnp.split(jax.lax.ragged_dot(rows, w13, sizes), 2,
                         axis=-1)
    out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w2, sizes)
    out = jnp.take(out, jnp.argsort(order), axis=0).reshape(t, k, -1)
    return jnp.einsum("tkh,tk->th", out.astype(jnp.float32),
                      weights).astype(u.dtype)


def test_glm_is_served_by_the_programs_it_was(monkeypatch):
    """GLM-4.7-Flash through the generalised `dropless_expert_ffn`
    and the one runner (`state_runner`): its decode and prefill lower to the same text as
    with the expert function of before, the outputs of a whole-range
    share (`first=0`) are bit-equal to the plain call's, and both MLA
    scales are 1 and multiply nothing."""
    cfg = glm.Glm4MoeLiteConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=8, num_experts_per_tok=2,
        max_position_embeddings=128)
    assert cfg.mla_q_scale == 1 and cfg.mla_kv_scale == 1
    paddle.seed(27)
    model = glm.Glm4MoeLiteForCausalLM(cfg)
    model.eval()
    runner = mr.runner_for(model)
    assert isinstance(runner, state_runner.StateRunner)
    assert runner.pool_layers == 3
    pool = jnp.zeros((3, 16, 4, 128))
    i32 = jnp.int32
    decode = (runner.params, jnp.zeros((4,), i32), jnp.zeros((4,), i32),
              (pool,), jnp.zeros((4, 8), i32), jnp.ones((4,), i32),
              jnp.zeros((4,)), jnp.zeros((4,), i32),
              jnp.zeros((4,), jnp.uint32))
    prefill = (runner.params, jnp.zeros((1, 16), i32), jnp.int32(5),
               (pool,), jnp.zeros((8,), i32), jnp.float32(0), jnp.int32(0),
               jnp.uint32(0))

    def texts():
        return [jax.jit(functools.partial(fn, block_size=4),
                        donate_argnums=(3,)).lower(*args).as_text()
                for fn, args in ((runner.decode_step, decode),
                                 (runner.prefill_step, prefill))]

    now = texts()
    assert "multiply" in now[0]
    monkeypatch.setattr(glm, "dropless_expert_ffn",
                        _old_dropless_expert_ffn)
    assert texts() == now
    monkeypatch.undo()
    # a share that holds the whole range gives the plain call's bits
    rng = np.random.RandomState(7)
    f = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    u, w13, w2 = f(32, 16), f(6, 16, 8), f(6, 4, 16)
    idx, w = dropless.sigmoid_topk_route(u, f(16, 6), f(6) * 0.1, 2, 1.8)
    plain = dropless.dropless_expert_ffn(u, idx, w, w13, w2)
    np.testing.assert_array_equal(
        plain, dropless.dropless_expert_ffn(u, idx, w, w13, w2, first=0))
    np.testing.assert_array_equal(
        plain, _old_dropless_expert_ffn(u, idx, w, w13, w2))
