"""ISSUE 38: Mellum2-12B-A2.5B (`mellum`) — sliding-window and full
attention layers in ONE paged cache whose blocks differ in lifetime by
layer group, the paged kernel that walks the window alone, rotary by
layer type, the softmax-top-k-renormalised router, and the engine against
the plain float32 reference of the benchmark (`tpubench/models/mellum.py`)
at toy widths with seeded weights on the CPU.

What is compared with what: (a) the model's own full forward with
`reference_logits`; (b) `LLMEngine` (a prompt longer than two windows,
decode to five, through the tables of four cache groups, with evictions
and re-prefills in the middle) with the reference by
`teacher_forced_deficits`, the programs returning tokens and not logits;
(c) the windowed kernel in the interpreter with the dense gather; (d) the
cache's bookkeeping alone over a 500-step decode; (e) the YaRN table
with its closed form; (f) the router with a numpy rule; and the programs
that were, pinned.
"""
import functools
import hashlib
import math
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
from paddle_tpu.inference.serving.kv_cache import NULL_BLOCK, PagedKVCache
from paddle_tpu.text.models import lfm2_moe as lfm
from paddle_tpu.text.models import mellum as ml
from paddle_tpu.text.models.common import swiglu
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from tpubench.models import mellum as fam  # noqa: E402

# two periods S S S F S S S F; window 8, block 4: a window group holds at
# most 8 / 4 + 2 = 4 blocks; YaRN over 16 original positions, factor 4
W, BS = 8, 4
TOY = dict(initializer_range=0.11, vocab_size=256, hidden_size=64,
           moe_intermediate_size=32, num_hidden_layers=8,
           layer_types=ml.PUBLISHED_LAYER_TYPES[:8], num_attention_heads=8,
           num_key_value_heads=2, head_dim=16, num_experts=8,
           num_experts_per_tok=2, sliding_window=W,
           max_position_embeddings=128,
           yarn_original_max_position_embeddings=16, yarn_factor=4.0,
           qk_norm_init=2.0)
LIMITS = {"logit_margin": 1e-3, "logit_mean_margin": 1e-4}
PROMPT_LENS = (20, 5, 33, 9, 17)      # 20, 33, 17: longer than two windows
HELD_MOST = math.ceil(W / BS) + 2


@pytest.fixture(scope="module")
def toy():
    cfg = ml.MellumConfig(**TOY)
    paddle.seed(38)
    model = ml.MellumForCausalLM(cfg)
    model.eval()
    params = jax.tree_util.tree_map(lambda p: p._value,
                                    model.model._params_tree())
    return cfg, model, params


def _engine(model, **kw):
    kw = {"max_batch": 3, "block_size": BS, "num_blocks": 200,
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


def _drive(eng, check):
    """Run the engine to the end, `check(eng)` after every step."""
    while eng.has_unfinished():
        eng.step()
        check(eng)


def _window_groups_within_bound(eng):
    for seq in eng.cache._seqs.values():
        held = [seq.hi - lo for lo in seq.lo]
        assert max(held[1:]) <= HELD_MOST, held
        # the table says what is held, and nothing else
        assert [(r != NULL_BLOCK).sum() for r in seq.rows] == held
    used = sum(len(b) for b in eng.cache.allocator._owned.values())
    assert used == eng.cache.allocator.used_blocks \
        == cmon.stat_get("serve/kv_blocks/used")


# -- (a) model against reference ------------------------------------------------

def test_reference_equals_the_models_full_forward(toy):
    cfg, model, params = toy
    assert cfg.count("sliding_attention") == 6
    assert model.model.attention_cache == (
        (1, 0, W), (1, 1, W), (2, 0, W), (0, 0, None),
        (2, 1, W), (3, 0, W), (3, 1, W), (0, 1, None))
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 48))
    out = model(paddle.to_tensor(ids)).numpy()
    for row, got in zip(ids, out):
        ref = np.asarray(fam.reference_logits(
            params, jnp.asarray(row), cfg, q_block=16))
        np.testing.assert_allclose(got, ref, atol=2e-4)
    part = np.asarray(fam.reference_logits(
        params, jnp.asarray(ids[0]), cfg, start=30, n_rows=4, q_block=16))
    np.testing.assert_allclose(part, out[0, 30:34], atol=2e-4)


def test_dense_attention_reads_only_the_window(toy):
    """`attend_dense`, query blocks that meet the key blocks one of
    their queries can see under a running softmax, equals the
    whole-square mask, with a window below, at and above the
    sequence's length and key blocks smaller and larger than the
    query blocks."""
    rng = np.random.RandomState(3)
    s, hq, hkv, d = 64, 4, 2, 8
    q = jnp.asarray(rng.randn(s, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(s, hkv * d), jnp.float32)
    v = jnp.asarray(rng.randn(s, hkv * d), jnp.float32)
    pos = np.arange(s)
    for window in (None, 1, 8, 20, 64, 100):
        seen = pos[:, None] >= pos[None, :]
        if window is not None:
            seen &= pos[:, None] - pos[None, :] < window
        kk = np.repeat(np.asarray(k).reshape(s, hkv, d), hq // hkv, 1)
        vv = np.repeat(np.asarray(v).reshape(s, hkv, d), hq // hkv, 1)
        sc = np.einsum("qhd,khd->hqk", np.asarray(q), kk) / math.sqrt(d)
        sc = np.where(seen, sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True),
                         vv).reshape(s, hq * d)
        for q_block, k_block in ((8, 16), (64, 8), (16, 64), (8, 4)):
            got = ml.attend_dense(q, k, v, window=window, q_block=q_block,
                                  k_block=k_block)
            np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_what_the_config_refuses():
    for bad in (dict(tie_word_embeddings=True), dict(norm_topk_prob=False),
                dict(num_key_value_heads=3), dict(num_hidden_layers=7),
                dict(layer_types=("sliding_attention",) * 8),
                dict(layer_types=("sliding_attention",) * 2
                     + ("full_attention",) * 6)):
        with pytest.raises(ValueError):
            ml.MellumConfig(**{**TOY, **bad})


# -- (b) engine against reference ------------------------------------------------

def test_engine_prefill_then_decode_against_the_reference(toy):
    """Five requests through three slots: prompts longer than two
    windows prefilled (their early rows to the NULL block), decode to
    beyond five windows, window and full layers through four tables."""
    cfg, model, params = toy
    prompts = _prompts(cfg)
    eng = _engine(model)
    assert isinstance(eng.runner, state_runner.StateRunner)
    assert eng.runner.cache_groups == (None, W, W, W)
    # ONE pool pair, two layers deep (a group's), blocks of one size
    assert [p.shape for p in eng.cache.pools] == [(2, 200, BS, 32)] * 2
    assert cmon.stat_get("serve/kv/groups") == 4
    assert cmon.stat_get("serve/kv/window") == W
    names = ("serve/kv/window_blocks_freed", "serve/kv/window_blocks_held",
             "serve/kv/window_blocks_least", "serve/kv/full_blocks_held",
             "serve/attn/steps_windowed", "serve/attn/steps")
    before = [cmon.stat_get(n) for n in names]
    rids = [eng.add_request(p, SamplingParams(max_new_tokens=24))
            for p in prompts]
    _drive(eng, _window_groups_within_bound)
    outs = [eng.get_request(r).output_ids for r in rids]
    assert [len(o) for o in outs] == [24] * 5
    assert max(len(p) + 24 for p in prompts) > 5 * W
    assert _worst(params, cfg, prompts, outs) <= 1e-4
    freed, held, least, full, windowed, steps = (
        cmon.stat_get(n) - b for n, b in zip(names, before))
    assert freed > 0 and windowed == steps > 0
    assert least <= held <= 1.5 * least and full > 0
    assert eng.check_drained() == {}
    assert eng.cache.allocator.used_blocks == 0 and not eng.cache._seqs


def test_a_long_prompt_is_granted_a_windows_blocks(toy):
    """A prefill of P > window tokens is granted, for a window group,
    the blocks of its newest `window + 2` positions and no others."""
    _, model, _ = toy
    eng = _engine(model, max_batch=1)
    # admission: 10 blocks in the full group, the blocks of positions
    # 30.. (40 - window - 2) in each window group, one more a group
    assert eng.cache.blocks_needed(40) == 10 + 3 * 3
    assert eng.cache.blocks_needed(40, 1) == 11 + 3 * 4
    eng.add_request(list(range(1, 41)), SamplingParams(max_new_tokens=3))
    used = []
    real = eng._prefill
    eng._prefill = lambda req: (used.append(
        eng.cache.allocator.used_blocks), real(req))[1]
    eng.step()
    assert used == [19]
    seq, = eng.cache._seqs.values()
    # then growth to 42 tokens: 11 blocks in the full group, positions
    # 32.. in the others
    assert seq.hi == 11 and seq.lo == [0, 8, 8, 8]
    assert (seq.rows[1:, :8] == NULL_BLOCK).all()
    assert (seq.rows[:, 8:11] != NULL_BLOCK).all()


def test_an_evicted_sequence_decodes_what_it_would_have(toy):
    """A pool too small for the load: requests are evicted mid-decode,
    re-admitted and re-prefilled (window groups granted their window
    alone), and emit the tokens of an engine that never evicts; the
    reference agrees; nothing leaks."""
    cfg, model, params = toy
    prompts = _prompts(cfg, seed=4)
    sp = SamplingParams(max_new_tokens=20)
    want = _engine(model).generate(prompts, sp)
    before = cmon.stat_get("serve/evictions")
    eng = _engine(model, num_blocks=36)
    rids = [eng.add_request(p, sp) for p in prompts]
    _drive(eng, _window_groups_within_bound)
    assert cmon.stat_get("serve/evictions") > before
    outs = [eng.get_request(r).output_ids for r in rids]
    assert outs == want
    assert _worst(params, cfg, prompts, outs) <= 1e-4
    assert eng.check_drained() == {}


def test_aborts_leave_no_block_and_run_ahead_frees_beside_the_device(toy):
    """`run_ahead=True` with a full batch: the freeing and the growth
    run in `_prepare_ahead`, a step ahead of the dispatch in flight,
    and the tokens stay those of the plain engine; requests aborted
    while they run or wait give every block back."""
    cfg, model, params = toy
    prompts = _prompts(cfg, seed=6, lens=(20, 22, 33, 18, 9))
    sp = SamplingParams(max_new_tokens=22)
    want = _engine(model, max_batch=5).generate(prompts[:3], sp)
    eng = _engine(model, run_ahead=True)
    rids = [eng.add_request(p, sp) for p in prompts]
    ahead = 0
    for _ in range(12):
        ahead += eng._inflight is not None
        eng.step()
        _window_groups_within_bound(eng)
    assert ahead >= 8
    eng.abort_request(rids[1])            # running, a dispatch in flight
    eng.abort_request(rids[4])            # waiting
    _drive(eng, _window_groups_within_bound)
    outs = [eng.get_request(r).output_ids for r in rids]
    assert outs[0] == want[0] and outs[2] == want[2]
    assert outs[1] == want[1][:len(outs[1])] and len(outs[1]) < 22
    assert outs[4] == [] and len(outs[3]) == 22
    assert eng._inflight is None and eng.check_drained() == {}
    assert eng.cache.allocator.used_blocks == 0


@pytest.mark.parametrize("kw,what", [
    (dict(spec_k=2), "rejected draft"), (dict(prefix_cache=True),
                                         "shared prefix")])
def test_prefix_cache_and_speculation_are_refused_with_the_reason(
        toy, kw, what):
    _, model, _ = toy
    with pytest.raises(NotImplementedError, match=what):
        _engine(model, **kw)


# -- the check reads planted faults -----------------------------------------------

def _fault_window_off_by_a_block(eng, mp):
    """Decode attends a window one block short."""
    real = pa.paged_attention_reference
    mp.setattr(pa, "paged_attention_reference",
               lambda *a, window=None, **kw: real(
                   *a, window=window and window - BS, **kw))


def _fault_default_rotary_in_a_full_layer(eng, mp):
    real = ml.rope_tables
    mp.setattr(ml, "rope_tables",
               lambda cfg, kind: real(cfg, "sliding_attention"))


def _fault_no_attention_factor(eng, mp):
    real = ml.rope_tables
    mp.setattr(ml, "rope_tables", lambda cfg, kind: (real(cfg, kind)[0], 1.0))


def _fault_no_window_in_prefill(eng, mp):
    real = ml.attend_dense
    mp.setattr(ml.MellumModel, "attend_dense", staticmethod(
        lambda q, k, v, window=None: real(q, k, v)))


def _fault_not_renormalised(eng, mp):
    real = ml.topk_route
    mp.setattr(ml, "topk_route", lambda *a: real(*a[:-1], False))


def _fault_freed_a_block_early(eng, mp):
    """The cache gives a window group's blocks back one block too
    soon: the kernel's walk then meets the NULL block."""
    real = PagedKVCache._first_block
    mp.setattr(PagedKVCache, "_first_block",
               lambda self, n, w: real(self, n + 2 * BS, w))


FAULTS = [_fault_window_off_by_a_block,
          _fault_default_rotary_in_a_full_layer, _fault_no_attention_factor,
          _fault_no_window_in_prefill, _fault_not_renormalised,
          _fault_freed_a_block_early]


@pytest.mark.parametrize("plant", FAULTS,
                         ids=[f.__name__[7:] for f in FAULTS])
def test_reference_catches_what_the_programs_must_not_do(
        toy, monkeypatch, plant):
    cfg, model, params = toy
    prompts = _prompts(cfg, seed=5)
    eng = _engine(model)
    plant(eng, monkeypatch)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=20))
    # the honest engine reads <= 1e-4 (above); the toy cell's per-token
    # limit is 1e-3
    assert _worst(params, cfg, prompts, outs) > 0.01


# -- (c) the paged kernel with a window -------------------------------------------

@pytest.mark.parametrize("walk", ["one-group", "fallback"])
@pytest.mark.parametrize("hq,hkv,dtype,tol", [
    (4, 4, jnp.float32, 2e-5), (8, 1, jnp.float32, 2e-5),
    (8, 1, jnp.bfloat16, 2e-2)])
def test_the_kernel_walks_the_window_alone(hq, hkv, dtype, tol, walk,
                                           monkeypatch):
    """`paged_attention(window=)` in the interpreter against the dense
    gather, at contexts below, at, one above and a page above the
    window, a window's first key mid-page, and a context at the end of
    a 512-row group counted from position 0 (where the window sat in
    one such group), 1 and 8 query heads a K/V head; the
    table's columns before the window name the NULL block, whose rows
    are NaN: never copied, never read. `one-group`: a window of 40 in
    pages of 8, ONE group of ceil(40 / 8) + 1 pages a sequence from
    the window's first page; `fallback`: a window whose tile passes
    the limit, in 128-row groups (the rule's least) from that page:
    a window of 200 over two or three."""
    rng = np.random.RandomState(hq + hkv)
    b, d, n, bs, maxb = 8, 32, 300, 8, 66
    if walk == "one-group":
        window, rows = 40, 48
    else:
        monkeypatch.setattr(pa, "_WINDOW_TILE_BYTES", 1)
        monkeypatch.setattr(pa, "_TILE_BYTES", 1)
        window, rows = 200, 128
    lens = np.asarray([1, window - 1, window, window + 1, window + bs,
                       2 * window + 5, 512, 517], np.int32)
    q = jnp.asarray(rng.randn(b, hq, d), dtype)
    kp = jnp.asarray(rng.randn(n, bs, hkv, d), dtype)
    vp = jnp.asarray(rng.randn(n, bs, hkv, d), dtype)
    tables = rng.permutation(n - 1)[:b * maxb // 2].reshape(b, -1) + 1
    tables = np.concatenate([tables, tables[:, ::-1]], 1).astype(np.int32)
    want = pa.paged_attention_reference(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(lens), sm_scale=0.2,
        window=window)
    # the dense gather with a window IS the whole-context one over a
    # context cut to the window (a check of the reference itself)
    cut = pa.paged_attention_reference(
        q, kp, vp, jnp.asarray(np.stack([
            np.roll(t, -(max(0, n_ - window) // bs)) for t, n_ in
            zip(tables, lens)])),
        jnp.asarray(lens - np.maximum(0, lens - window) // bs * bs),
        sm_scale=0.2, window=window)
    f32 = lambda a: np.asarray(a, np.float32)        # noqa: E731
    np.testing.assert_allclose(f32(cut), f32(want), atol=tol)
    freed = tables.copy()
    for row, n_ in zip(freed, lens):
        row[:max(0, n_ - window) // bs] = NULL_BLOCK
    nan = jnp.asarray(np.nan, dtype)
    counter = f"kernels/paged/rows_{rows}"
    before = cmon.stat_get(counter)
    got = pa.paged_attention(
        q, kp.at[NULL_BLOCK].set(nan), vp.at[NULL_BLOCK].set(nan),
        jnp.asarray(freed), jnp.asarray(lens), sm_scale=0.2,
        interpret=True, window=window)
    assert cmon.stat_get(counter) - before == 1
    np.testing.assert_allclose(f32(got), f32(want), atol=tol)
    # a window as long as every context is no window
    whole = pa.paged_attention(q, kp, vp, jnp.asarray(tables),
                               jnp.asarray(lens), sm_scale=0.2,
                               interpret=True, window=maxb * bs)
    np.testing.assert_allclose(f32(whole), f32(pa.paged_attention_reference(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(lens), sm_scale=0.2)),
        atol=tol)
    with pytest.raises(ValueError, match="window"):
        pa._paged_call(q[:, None].repeat(2, 1), kp, vp, jnp.asarray(tables),
                       jnp.asarray(lens), 0.2, True, window=8)


# sha256 of `str(jax.make_jaxpr(...))` of calls without a window at 4
# query heads a K/V head, decode and three verify slots, taken on the
# parent commit (2c397c0): the window's own walk leaves them as they were
NO_WINDOW_JAXPRS = {(1, "float32"): "2306afb9b2218748",
                    (1, "bfloat16"): "611fc825113c7985",
                    (3, "float32"): "4d201bc788bd6205",
                    (3, "bfloat16"): "64218e4574be94f6"}


@pytest.mark.parametrize("t_q,dtype", list(NO_WINDOW_JAXPRS))
def test_a_call_without_a_window_traces_the_kernel_that_was(t_q, dtype):
    q = jnp.zeros((4, t_q, 8, 32) if t_q > 1 else (4, 8, 32), dtype)
    pool = jnp.zeros((24, 8, 2, 32), dtype)
    fn = pa.paged_attention_multi if t_q > 1 else pa.paged_attention
    text = str(jax.make_jaxpr(
        lambda q, k, v, t, n: fn(q, k, v, t, n, sm_scale=0.25))(
            q, pool, pool, jnp.zeros((4, 5), jnp.int32),
            jnp.ones((4,), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == NO_WINDOW_JAXPRS[t_q, dtype]


def test_decode_through_the_windowed_kernel_emits_the_dense_tokens(
        monkeypatch):
    """Under the interpreter the engine takes the Pallas paged kernel
    by itself, for window and full layers alike, and emits the dense
    engine's tokens. One period of the layers (S S S F: four cache
    groups of one layer), for the interpreter's sake."""
    paddle.seed(39)
    model = ml.MellumForCausalLM(ml.MellumConfig(**{
        **TOY, "num_hidden_layers": 4,
        "layer_types": ml.PUBLISHED_LAYER_TYPES[:4]}))
    model.eval()
    prompts = [list(range(3, 25)), [9, 8]]
    names = ("serve/attn/steps", "serve/attn/steps_paged",
             "serve/attn/steps_windowed")

    def run(**kw):
        before = [cmon.stat_get(n) for n in names]
        eng = _engine(model, **kw)
        out = eng.generate(prompts, SamplingParams(max_new_tokens=5))
        return eng, out, [cmon.stat_get(n) - b
                          for n, b in zip(names, before)]

    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)
    dense, want, counts = run()
    assert not dense.use_kernel and counts == [4, 0, 4]
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    eng, got, counts = run()
    assert eng.use_kernel and eng._kernel_interpret
    assert got == want and counts == [4, 4, 4]


# -- (d) the cache's bookkeeping alone ---------------------------------------------

def test_window_groups_hold_a_windows_blocks_over_500_steps():
    """No model: sequences of mixed lengths admitted, grown a token a
    step for 500 steps and released at their ends, in a pool of 299
    blocks (kept whole, four sequences of 700 tokens in four groups
    would take 700). A window group never
    holds more than `ceil(W / BS) + 2` blocks, a freed block is taken
    by another sequence, the gauge is exact, and the pool drains."""
    rng = np.random.RandomState(0)
    window, bs = 64, 16
    most = window // bs + 2
    cache = PagedKVCache(2, rows=(8, 8), block_size=bs, num_blocks=300,
                         groups=(None, window, window, window),
                         max_seq_len=1024)
    alloc = cache.allocator
    live, ends, ever = {}, {}, {}
    reused = n_admitted = 0
    for step in range(500):
        while len(live) < 4:
            owner, n = f"s{n_admitted}", int(rng.choice([3, 40, 150, 333]))
            assert cache.can_admit(n) and cache.admit(owner, [0] * n) == 0
            live[owner], ends[owner] = n, n + int(rng.randint(20, 400))
            n_admitted += 1
        for owner in list(live):
            live[owner] += 1
            assert cache.grow(owner, live[owner] + 1)
            seq = cache._seqs[owner]
            held = [seq.hi - lo for lo in seq.lo]
            assert held[0] == math.ceil((live[owner] + 1) / bs)
            assert max(held[1:]) <= most
            # every position the next query can see is in a block
            first_seen = max(0, live[owner] - window) // bs
            assert (seq.rows[:, first_seen:seq.hi] != NULL_BLOCK).all()
            assert cache.held(owner) == (held[0], sum(held[1:]))
            for blk in seq.rows[seq.rows != NULL_BLOCK]:
                reused += ever.setdefault(int(blk), owner) != owner
                ever[int(blk)] = owner
            if live[owner] >= ends[owner]:
                assert cache.release(owner) == sum(held)
                del live[owner]
        assert alloc.used_blocks == sum(
            sum(cache.held(o)) for o in live) \
            == cmon.stat_get("serve/kv_blocks/used")
    assert reused > 100 and n_admitted > 8
    assert max(live.values()) > 4 * window
    for owner in list(live):
        cache.release(owner)
    assert alloc.used_blocks == 0 and alloc.audit_leaks() == {}
    assert cache.window_blocks_least(1000) == 3 * 5
    assert cache.window_blocks_least(10) == 3 * 1


def test_a_grouped_cache_defrags_and_refuses_what_it_cannot_share():
    cache = PagedKVCache(2, rows=(8, 8), block_size=4, num_blocks=64,
                         groups=(None, 8, 8), max_seq_len=64)
    cache.admit("a", [0] * 30)
    cache.admit("b", [0] * 9)
    cache.release("a")
    before = cache.block_table("b", 16).copy()
    assert cache.defrag() > 0
    after = cache.block_table("b", 16)
    assert ((before == NULL_BLOCK) == (after == NULL_BLOCK)).all()
    assert sorted(after[after != NULL_BLOCK]) == list(range(1, 10))
    assert sorted(cache.allocator.owned("b")) == list(range(1, 10))
    assert not cache.grow("b", 10 ** 4) and cache.grow("b", 12)
    with pytest.raises(ValueError, match="several cache groups"):
        PagedKVCache(2, rows=(8, 8), groups=(None, 8), prefix_cache=True,
                     num_blocks=8, max_seq_len=64)


def test_a_cache_of_one_group_is_the_cache_it_was():
    """GPT-2's engine: one group, the allocator's lists for tables, a
    2-D batch of them; growth block by block as ever."""
    paddle.seed(27)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
        ffn_hidden=64, max_seq_len=32, dropout=0.0))
    model.eval()
    eng = LLMEngine(model, max_batch=2, block_size=4, num_blocks=16)
    assert eng.cache.groups == (None,) and eng.cache._seqs is None
    assert [p.shape for p in eng.cache.pools] == [(2, 16, 4, 32)] * 2
    rid = eng.add_request([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=6))
    eng.step()
    assert eng._batch_arrays()[2].shape == (2, 8)
    assert eng.cache.allocator.owned(rid) == [1, 2]
    assert eng.cache.held(rid) == (2, 0) and eng.cache.short(rid, 13) == 2
    assert eng.cache.blocks_needed(9, 1) == 3 + 1
    while eng.has_unfinished():
        eng.step()
    assert eng.check_drained() == {}
    assert cmon.stat_get("serve/kv/groups") == 1


# -- (e) rotary by layer type --------------------------------------------------------

def test_the_yarn_table_is_the_closed_form():
    """The published numbers: dim(32) = 18.08 and dim(1) = 34.98, so a
    dimension below 18 keeps its frequency, one above 35 has it divided
    by 16, between them the ramp's share; cos and sin times 0.1 ln 16 +
    1. The program's table and the reference's own."""
    cfg = ml.MellumConfig()
    theta, d = 500000.0, 128

    def dim(r):
        return d * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(theta))

    assert (math.floor(dim(32)), math.ceil(dim(1))) == (18, 35)
    for table in (ml.rope_tables, fam.rotary_inv_freq):
        inv, factor = table(cfg, "full_attention")
        assert factor == 1.2772588722239782
        assert abs(factor - (0.1 * math.log(16) + 1)) < 1e-12
        for i, share in ((10, 0.0), (26, 8 / 17), (50, 1.0)):
            want = theta ** (-2 * i / d) * ((1 - share) + share / 16)
            assert abs(inv[i] / want - 1) < 1e-6, (i, inv[i], want)
        plain, one = table(cfg, "sliding_attention")
        assert one == 1.0
        np.testing.assert_allclose(
            plain, theta ** (-np.arange(64) / 64.0), rtol=1e-6)
    # the tables do not depend on the length served
    short = ml.MellumConfig(max_position_embeddings=4096)
    np.testing.assert_array_equal(ml.rope_tables(short, "full_attention")[0],
                                  ml.rope_tables(cfg, "full_attention")[0])


# -- (f) the router -------------------------------------------------------------------

def test_the_route_is_softmax_top_k_renormalised(toy):
    """1000 seeded rows against a numpy rule: softmax over the
    experts, the k largest (ties to the smaller id), weights over
    their sum; and `moe_ffn` against a per-token loop."""
    rng = np.random.RandomState(8)
    u = jnp.asarray(rng.randn(1000, 64), jnp.float32)
    w_r = jnp.asarray(rng.randn(64, 16) * 0.3, jnp.float32)
    idx, w = dropless.topk_route(u, w_r, None, 4, 1.0, jax.nn.softmax, True)
    logits = np.asarray(u, np.float64) @ np.asarray(w_r, np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    order = np.argsort(-s, axis=-1, kind="stable")[:, :4]
    assert (np.asarray(idx) == order).all()
    picked = np.take_along_axis(s, order, -1)
    np.testing.assert_allclose(np.asarray(w),
                               picked / picked.sum(-1, keepdims=True),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    # ties: equal scores go to the smaller ids, in equal shares
    idx, w = dropless.topk_route(jnp.zeros((3, 64)), w_r, None, 4, 1.0,
                                 jax.nn.softmax, True)
    assert (np.asarray(idx) == np.arange(4)).all()
    np.testing.assert_allclose(np.asarray(w), 0.25, atol=1e-7)
    # the two published cases are cases of the one rule
    b = jnp.asarray(rng.randn(16) * 0.1, jnp.float32)
    for named, score, renorm in (
            (dropless.sigmoid_topk_route, jax.nn.sigmoid, True),
            (dropless.softmax_topk_route, jax.nn.softmax, False)):
        for got, want in zip(named(u, w_r, b, 4, 1.8), dropless.topk_route(
                u, w_r, b, 4, 1.8, score, renorm)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    cfg, _, params = toy
    mp = params["layers"][3]["moe"]
    u = jnp.asarray(rng.randn(6, 64), jnp.float32)
    out, counts = ml.moe_ffn(u, mp, cfg)
    idx, w = dropless.topk_route(u, mp["router_w"], None, 2, 1.0,
                                 jax.nn.softmax, True)
    assert int(counts.sum()) == 12
    for t in range(6):
        want = sum(float(w[t, i]) * np.asarray(swiglu(
            u[t], mp["w13"][int(idx[t, i])], mp["w2"][int(idx[t, i])]))
            for i in range(2))
        np.testing.assert_allclose(np.asarray(out[t]), want, atol=1e-5)


# -- the programs that were, pinned ---------------------------------------------------

# sha256 of the lowered StableHLO text of the state runner's prefill and
# decode for LFM2 (no windowed attention, one cache group) and of the
# decode's jaxpr with the paged kernel in it, taken on the parent commit
# (7f2063d; decode's jaxpr again when the paged kernel's page copies became
# a rolled loop): the runner that now reads cache groups from a model
# serves a model without them the programs it served
LFM2_PROGRAMS = {("decode_step", "text"): "07ff0e0d3c1c7b79",
                 ("decode_step", "jaxpr"): "7b97a6cd83bf96d0",
                 ("prefill_step", "text"): "eef1e94d8e6cbe39",
                 ("prefill_step", "jaxpr"): "b64a57d4ed3a19c3"}


@pytest.mark.parametrize("step,form", list(LFM2_PROGRAMS))
def test_lfm2_traces_to_the_programs_it_had(step, form):
    paddle.seed(27)
    model = lfm.Lfm2MoeForCausalLM(lfm.Lfm2MoeConfig(
        initializer_range=0.11, vocab_size=256, hidden_size=64,
        intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=7,
        layer_types=lfm.PUBLISHED_LAYER_TYPES[:7], num_attention_heads=8,
        num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
        max_position_embeddings=128))
    model.eval()
    runner = mr.runner_for(model)
    assert runner.cache_groups == (None,) and runner.pool_layers == 2
    i32 = jnp.int32
    pools = (jnp.zeros((2, 16, 4, 16)), jnp.zeros((2, 16, 4, 16)),
             jnp.zeros((5, 4, 2, 64)))
    args = {
        "decode_step": (
            runner.params, jnp.zeros((4,), i32), jnp.zeros((4,), i32), pools,
            jnp.zeros((4, 8), i32), jnp.ones((4,), i32), jnp.zeros((4,)),
            jnp.zeros((4,), i32), jnp.zeros((4,), jnp.uint32)),
        "prefill_step": (
            runner.params, jnp.zeros((1, 16), i32), jnp.int32(5), pools,
            jnp.zeros((8,), i32), jnp.float32(0), jnp.int32(0),
            jnp.uint32(0), jnp.int32(1))}[step]
    if form == "text":
        text = jax.jit(functools.partial(getattr(runner, step), block_size=4),
                       donate_argnums=(3,)).lower(*args).as_text()
    else:
        kernel = {"use_kernel": True, "interpret": True} \
            if step == "decode_step" else {}
        text = str(jax.make_jaxpr(functools.partial(
            getattr(runner, step), block_size=4, **kernel))(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == LFM2_PROGRAMS[step, form]
