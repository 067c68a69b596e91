"""ISSUE 11: the TPU-native serving engine.

Paged KV cache (block allocator invariants, defrag), the
continuous-batching scheduler (admit/evict ordering, preemption
replay), the ragged paged-attention kernel (interpret-mode parity vs
the dense reference at mixed lengths), the LLMEngine e2e contract
(>= 8 concurrent mixed-length greedy requests bit-identical to the
sequential unbatched full-re-forward loop, zero leaked blocks after
drain), the serve_admit/serve_decode chaos sites (request flood
survives injected OOM without wedging or leaking), the PTA07x
block-leak sanitizer (runtime + static), and the README doc-drift
gate over inference/serving/.
"""
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor as cmon
from paddle_tpu.inference.serving import (BlockAllocator, LLMEngine,
                                          NULL_BLOCK, PagedKVCache,
                                          SamplingParams)
from paddle_tpu.inference.serving.scheduler import (FINISHED, Request,
                                                    Scheduler,
                                                    WAITING)
from paddle_tpu.monitor import chaos
from paddle_tpu.monitor import sanitize as msan
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_model(vocab=128, hidden=64, layers=2, heads=4, seq=64,
               init=0.35):
    """Small gpt2 with a WIDE initializer so greedy decodes produce
    varied (non-degenerate) token sequences — a stronger parity
    check than a near-uniform model that repeats one argmax."""
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_heads=heads,
                    ffn_hidden=2 * hidden, max_seq_len=seq,
                    dropout=0.0, use_flash_attention=False,
                    initializer_range=init)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def ref_greedy(model, prompt, n):
    """Sequential unbatched decode: full re-forward per token — the
    token-identity reference the engine must reproduce. The input is
    zero-padded to max_seq_len so the eager forward keeps ONE shape
    (row t of a causal model never sees rows > t, so padding can't
    change the argmax'd row — and the suite doesn't pay a fresh XLA
    compile per distinct sequence length)."""
    smax = model.config.max_seq_len
    ids = list(prompt)
    out = []
    for _ in range(n):
        if len(ids) >= smax:
            break
        arr = np.zeros((1, smax), np.int32)
        arr[0, :len(ids)] = ids
        t = model(paddle.to_tensor(arr))
        nxt = int(np.argmax(np.asarray(t.numpy()[0, len(ids) - 1])))
        out.append(nxt)
        ids.append(nxt)
    return out


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_alloc_free_invariants(self):
        a = BlockAllocator(10)  # 9 usable + null
        assert a.free_blocks == 9 and a.used_blocks == 0
        got = a.alloc("r1", 4)
        assert len(got) == 4 and NULL_BLOCK not in got
        assert a.used_blocks == 4 and a.free_blocks == 5
        assert sorted(a.owned("r1")) == sorted(got)
        assert a.release("r1") == 4
        assert a.free_blocks == 9 and a.owned("r1") == []
        assert a.release("r1") == 0  # idempotent no-op

    def test_exhaustion_never_partial(self):
        a = BlockAllocator(6)
        assert a.alloc("r1", 3) is not None
        before = a.free_blocks
        assert a.alloc("r2", 4) is None  # 2 free < 4: no grant
        assert a.free_blocks == before and a.owned("r2") == []
        assert a.alloc("r2", 2) is not None

    def test_block_ids_unique_across_owners(self):
        a = BlockAllocator(16)
        all_ids = a.alloc("a", 5) + a.alloc("b", 5) + a.alloc("c", 5)
        assert len(set(all_ids)) == 15

    def test_free_one_and_double_free(self):
        a = BlockAllocator(8)
        got = a.alloc("r", 3)
        a.free_one("r", got[1])
        assert a.free_blocks == 5  # 7 usable - 2 still held
        with pytest.raises(ValueError):
            a.free_one("r", got[1])  # double-free
        with pytest.raises(ValueError):
            a.free_one("other", got[0])  # foreign free

    def test_occupancy_gauges(self):
        a = BlockAllocator(8)
        a.alloc("r", 5)
        assert cmon.stat_get("serve/kv_blocks/used") == 5
        assert cmon.stat_get("serve/kv_blocks/free") == 2
        a.release("r")
        assert cmon.stat_get("serve/kv_blocks/used") == 0


class TestPagedKVCache:
    def test_geometry_and_admission(self):
        c = PagedKVCache(2, 4, 16, block_size=8, num_blocks=10)
        assert c.blocks_for_tokens(1) == 1
        assert c.blocks_for_tokens(8) == 1
        assert c.blocks_for_tokens(9) == 2
        # 9 usable blocks; prompt of 8 blocks + 1 lookahead fits
        assert c.can_admit(8 * 8)
        assert not c.can_admit(8 * 9)

    def test_block_table_padding(self):
        c = PagedKVCache(1, 2, 8, block_size=4, num_blocks=12)
        c.allocator.alloc("r", 3)
        row = c.block_table("r", 6)
        assert row.shape == (6,) and row.dtype == np.int32
        assert list(row[3:]) == [NULL_BLOCK] * 3
        assert NULL_BLOCK not in row[:3]
        with pytest.raises(ValueError):
            c.block_table("r", 2)  # table wider than max

    def test_defrag_compacts_and_preserves_contents(self):
        import jax.numpy as jnp

        c = PagedKVCache(1, 2, 4, block_size=2, num_blocks=12)
        a, b = c.allocator.alloc("a", 3), c.allocator.alloc("b", 3)
        # stamp each block with its id so moves are detectable
        c.k = jnp.arange(c.num_blocks, dtype=c.k.dtype).reshape(
            1, -1, 1, 1) * jnp.ones_like(c.k)
        c.v = 100.0 + c.k
        c.allocator.release("a")  # holes at the front
        stamps = {blk: float(c.k[0, blk, 0, 0]) for blk in b}
        moved = c.defrag()
        assert moved > 0
        newb = c.allocator.owned("b")
        assert sorted(newb) == [1, 2, 3]  # compacted to the front
        for old, new in zip(b, newb):
            assert float(c.k[0, new, 0, 0]) == stamps[old]
            assert float(c.v[0, new, 0, 0]) == stamps[old] + 100.0
        # free list contiguous after the compacted region
        assert sorted(c.allocator._free) == list(range(4, 12))
        assert c.defrag() == 0  # already compact


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

class TestSamplingParamsValidation:
    """ISSUE-13 satellite: every SamplingParams field is validated at
    the API edge — bad values must raise clear ValueErrors HERE, not
    crash (or silently misbehave) inside a compiled dispatch."""

    def test_negative_top_k_rejected(self):
        # a negative k used to flow uncaught into the compiled
        # sampler, which reads k <= 0 as "no filter": ignored in silence
        with pytest.raises(ValueError, match="top_k"):
            SamplingParams(top_k=-1)

    def test_non_int_top_k_rejected(self):
        for bad in (1.5, "5", True):
            with pytest.raises(ValueError, match="top_k"):
                SamplingParams(top_k=bad)

    def test_top_k_zero_and_numpy_int_ok(self):
        assert SamplingParams(top_k=0).top_k == 0
        assert SamplingParams(top_k=np.int32(7)).top_k == 7

    def test_seed_type_validated(self):
        for bad in (1.5, "7", None, True):
            with pytest.raises(ValueError, match="seed"):
                SamplingParams(seed=bad)
        assert SamplingParams(seed=np.int64(3)).seed == 3

    def test_stop_token_ids_element_types(self):
        with pytest.raises(ValueError, match="stop_token_ids"):
            SamplingParams(stop_token_ids=(1, "eos"))
        with pytest.raises(ValueError, match="stop_token_ids"):
            SamplingParams(stop_token_ids=[2.5])
        assert SamplingParams(
            stop_token_ids=(1, np.int32(2))).stop_token_ids == (1, 2)

    def test_eos_token_id_validated(self):
        with pytest.raises(ValueError, match="eos_token_id"):
            SamplingParams(eos_token_id="2")
        assert SamplingParams(eos_token_id=None).eos_token_id is None

    def test_deadline_validated(self):
        with pytest.raises(ValueError, match="deadline_s"):
            SamplingParams(deadline_s=0)
        with pytest.raises(ValueError, match="deadline_s"):
            SamplingParams(deadline_s=-1.5)
        assert SamplingParams(deadline_s=2.5).deadline_s == 2.5
        assert SamplingParams().deadline_s is None


def _mk_cache(num_blocks=32, block_size=4):
    return PagedKVCache(1, 2, 8, block_size=block_size,
                        num_blocks=num_blocks)


class TestScheduler:
    def test_fifo_admission_order(self):
        s = Scheduler(_mk_cache(), max_batch=2, max_seq_len=64)
        reqs = [Request([1] * 4, req_id=f"r{i}") for i in range(4)]
        for r in reqs:
            s.add(r)
        admitted = s.schedule()
        assert [r.req_id for r in admitted] == ["r0", "r1"]
        assert reqs[2].state == WAITING
        assert s.schedule() == []  # batch full
        s.finish(reqs[0])
        assert [r.req_id for r in s.schedule()] == ["r2"]

    def test_admission_respects_pool(self):
        s = Scheduler(_mk_cache(num_blocks=4, block_size=4),
                      max_batch=4, max_seq_len=64)
        s.add(Request([1] * 8, req_id="big"))   # 2 blocks + lookahead
        s.add(Request([1] * 8, req_id="second"))
        admitted = s.schedule()
        # 3 usable blocks: big (2+1 lookahead) fits, second must wait
        assert [r.req_id for r in admitted] == ["big"]
        assert len(s.waiting) == 1

    def test_eviction_picks_youngest_and_requeues_front(self):
        s = Scheduler(_mk_cache(), max_batch=3, max_seq_len=64)
        reqs = [Request([1] * 4, req_id=f"r{i}") for i in range(3)]
        for r in reqs:
            s.add(r)
        s.schedule()
        reqs[2].output_ids.append(7)  # progress to preserve
        victim = s._pick_victim()
        assert victim is reqs[2]  # youngest admitted
        before = cmon.stat_get("serve/evictions")
        s.evict(victim)
        assert cmon.stat_get("serve/evictions") == before + 1
        assert s.waiting[0] is reqs[2]       # front of the queue
        assert reqs[2].output_ids == [7]     # generation kept
        assert s.cache.allocator.owned("r2") == []

    def test_ensure_capacity_grows_and_evicts(self):
        cache = _mk_cache(num_blocks=5, block_size=4)  # 4 usable
        s = Scheduler(cache, max_batch=2, max_seq_len=64)
        r0, r1 = Request([1] * 8, req_id="r0"), \
            Request([1] * 4, req_id="r1")
        s.add(r0), s.add(r1)
        s.schedule()
        assert set(s.running.values()) == {r0, r1}  # 2 + 1 blocks
        r0.output_ids.extend([1] * 4)  # ctx 12 -> needs a 4th block
        assert s.ensure_capacity(r0)   # grows, evicting youngest r1
        assert len(cache.allocator.owned("r0")) == 4
        assert r1.state == WAITING and r1.evictions == 1
        assert s.waiting[0] is r1

    def test_self_eviction_when_pool_cannot_grow(self):
        cache = _mk_cache(num_blocks=4, block_size=4)  # 3 usable
        s = Scheduler(cache, max_batch=1, max_seq_len=64)
        r = Request([1] * 8, req_id="r")
        s.add(r)
        s.schedule()
        r.output_ids.extend([1] * 8)   # ctx 16 -> needs 5 > 3 usable
        assert not s.ensure_capacity(r)
        assert r.state == WAITING
        assert cache.allocator.used_blocks == 0

    def test_static_batching_drains_first(self):
        s = Scheduler(_mk_cache(), max_batch=2, max_seq_len=64,
                      static_batching=True)
        reqs = [Request([1] * 4, req_id=f"r{i}") for i in range(3)]
        for r in reqs:
            s.add(r)
        assert len(s.schedule()) == 2
        s.finish(reqs[0])
        assert s.schedule() == []  # batch not drained yet
        s.finish(reqs[1])
        assert [r.req_id for r in s.schedule()] == ["r2"]

    def test_abort_releases_everywhere(self):
        s = Scheduler(_mk_cache(), max_batch=1, max_seq_len=64)
        r0, r1 = Request([1] * 4, req_id="a"), \
            Request([1] * 4, req_id="b")
        s.add(r0), s.add(r1)
        s.schedule()
        s.abort(r1)  # still waiting
        assert r1 not in s.waiting and r1.finished
        s.abort(r0)  # running
        assert not s.running
        assert s.cache.allocator.used_blocks == 0

    def test_abort_waiting_removes_deque_entry_and_syncs_depth(self):
        """ISSUE-13 satellite regression: aborting a WAITING request
        must remove its deque entry AND re-sync serve/queue_depth in
        the SAME call — abort-while-queued is the router failover's
        hot path, and a stale entry would be re-admitted as a ghost
        after its record was exported elsewhere."""
        s = Scheduler(_mk_cache(), max_batch=1, max_seq_len=64)
        reqs = [Request([1] * 4, req_id=f"q{i}") for i in range(3)]
        for r in reqs:
            s.add(r)
        assert cmon.stat_get("serve/queue_depth") == 3
        s.abort(reqs[1])  # middle of the deque, never admitted
        assert reqs[1] not in s.waiting
        assert reqs[1].finished
        assert cmon.stat_get("serve/queue_depth") == 2
        # remaining order preserved; the ghost never admits
        admitted = s.schedule()
        assert [r.req_id for r in admitted] == ["q0"]
        s.abort(reqs[0]), s.abort(reqs[2])
        assert cmon.stat_get("serve/queue_depth") == 0
        assert s.cache.allocator.used_blocks == 0
        assert s.cache.allocator.audit_leaks([]) == {}


# ---------------------------------------------------------------------------
# ragged paged-attention kernel (interpret-mode CPU parity)
# ---------------------------------------------------------------------------

class TestPagedAttentionKernel:
    def _rand(self, b=4, h=4, d=32, bs=8, n=24, maxb=5, dtype=None):
        import jax.numpy as jnp

        rng = np.random.RandomState(0)
        dtype = dtype or jnp.float32
        q = jnp.asarray(rng.randn(b, h, d), dtype)
        kp = jnp.asarray(rng.randn(n, bs, h, d), dtype)
        vp = jnp.asarray(rng.randn(n, bs, h, d), dtype)
        bt = jnp.asarray(rng.randint(1, n, (b, maxb)), jnp.int32)
        return q, kp, vp, bt

    @pytest.mark.parametrize("lens", [
        (1, 1, 1, 1),            # single token everywhere
        (8, 16, 32, 40),         # exact block boundaries
        (1, 8, 9, 40),           # boundary +/- 1 mixed
        (37, 3, 23, 15),         # odd ragged lengths
    ])
    def test_interpret_parity_vs_dense(self, lens):
        import jax.numpy as jnp
        from paddle_tpu.incubate.nn.pallas.paged_attention import (
            paged_attention, paged_attention_reference)

        q, kp, vp, bt = self._rand()
        cl = jnp.asarray(np.array(lens, np.int32))
        out = paged_attention(q, kp, vp, bt, cl, sm_scale=0.2,
                              interpret=True)
        ref = paged_attention_reference(q, kp, vp, bt, cl,
                                        sm_scale=0.2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-6, atol=2e-6)

    def test_dead_blocks_never_read(self):
        """Grid-skipping proof: table slots past a sequence's context
        are dead — rewriting those pool blocks (and the whole rest of
        the pool) cannot change the output."""
        import jax.numpy as jnp
        from paddle_tpu.incubate.nn.pallas.paged_attention import (
            paged_attention)

        q, kp, vp, bt = self._rand(maxb=4, bs=8)
        cl = jnp.asarray(np.array([9, 3, 17, 8], np.int32))
        out = paged_attention(q, kp, vp, bt, cl, sm_scale=0.3,
                              interpret=True)
        # live (block, slot) pairs per the tables/contexts; poison
        # every other pool position with huge values
        live = np.zeros((kp.shape[0], kp.shape[1]), bool)
        bt_np, cl_np = np.asarray(bt), np.asarray(cl)
        for b in range(len(cl_np)):
            for t in range(cl_np[b]):
                live[bt_np[b, t // 8], t % 8] = True
        poison = jnp.where(jnp.asarray(live)[:, :, None, None], kp,
                           1e9)
        poison_v = jnp.where(jnp.asarray(live)[:, :, None, None], vp,
                             -1e9)
        out2 = paged_attention(q, poison, poison_v, bt, cl,
                               sm_scale=0.3, interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(out2))

    def test_bf16_pools(self):
        import jax.numpy as jnp
        from paddle_tpu.incubate.nn.pallas.paged_attention import (
            paged_attention, paged_attention_reference)

        q, kp, vp, bt = self._rand(dtype=jnp.bfloat16)
        cl = jnp.asarray(np.array([5, 17, 33, 40], np.int32))
        out = paged_attention(q, kp, vp, bt, cl, sm_scale=0.2,
                              interpret=True)
        ref = paged_attention_reference(q, kp, vp, bt, cl,
                                        sm_scale=0.2)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# ISSUE 30: a grid step takes G pages of one sequence
# ---------------------------------------------------------------------------

@pytest.fixture
def small_groups(monkeypatch):
    """The rule's least group, whatever the row: 128 rows, 8 pages of
    16 (toy rows would take 1024)."""
    from paddle_tpu.incubate.nn.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_TILE_BYTES", 1)
    assert pa._pages_per_group(16, 512) == 8


class TestPagedKernelPageGroups:
    """Block 16 -> groups of G = 8 pages (128 rows, `small_groups`). A
    table of 20 slots is one G does not divide (padded to 24: three
    groups); the tables hold the NULL block 0 past each context, as
    the engine's do."""
    BS, MAXB, N = 16, 20, 96
    # 1 token; ends inside the first page of the second group;
    # exactly one group; inside the last page; the full table
    LENS = (1, 130, 128, 307, 320)

    def _inputs(self, dtype, t=None, h=4, d=32):
        import jax.numpy as jnp

        rng = np.random.RandomState(11)
        b = len(self.LENS)
        q = jnp.asarray(
            rng.randn(*((b, h, d) if t is None else (b, t, h, d))), dtype)
        kp = jnp.asarray(rng.randn(self.N, self.BS, h, d), dtype)
        vp = jnp.asarray(rng.randn(self.N, self.BS, h, d), dtype)
        tables = np.zeros((b, self.MAXB), np.int32)
        nxt = 1
        for i, n in enumerate(self.LENS):
            used = min(self.MAXB, -(-(n + (t or 1) - 1) // self.BS))
            tables[i, :used] = nxt + np.arange(used)
            nxt += used
        assert nxt <= self.N
        return q, kp, vp, jnp.asarray(tables), jnp.asarray(
            np.array(self.LENS, np.int32))

    @pytest.mark.parametrize("block,row_bytes,pages", [
        (16, 1024, 64),    # 4 K/V heads of 128 (or 8 of 64) in bf16: 1024 rows
        (16, 1280, 32),    # a latent row of 640 bf16 values: 512 rows
        (16, 2048, 32),    # GPT-2's 16 x 64 in bf16: 512 rows
        (16, 4096, 16),    # ... in f32, the cell's: 256 rows
        (8, 4096, 32), (32, 4096, 8), (128, 4096, 2), (256, 4096, 1),
        (16, 8192, 8),     # 128 rows
        (16, 16384, 8),    # long rows: 128 rows and no fewer
        (16, 512, 64),     # short toy rows: 1024 rows and no more
        (8, 512, 64),      # ... and at most 64 pages
    ])
    def test_group_size_follows_the_block(self, block, row_bytes, pages):
        """One rule for both paged kernels: the largest power of two of
        rows whose tile of `row_bytes` rows fits in 1 MiB, between 128
        and 1024 rows, at most 64 pages."""
        from paddle_tpu.incubate.nn.pallas import paged_attention as pa

        assert pa._pages_per_group(block, row_bytes) == pages

    # (query heads, K/V heads, head dim, contexts, window, query slots):
    # the cells' layouts (rows of 1 KB in bf16: 1024-row groups of 64
    # pages) at toy depth
    CELL_LAYOUTS = {
        # ends inside a group's last page, on it, on the next group's
        # first page, on the third group's first
        "g4": (32, 8, 64, (1, 1020, 1024, 1025, 2049), None, None),
        "g5": (20, 4, 128, (1, 1020, 1024, 1025, 2049), None, None),
        "g8": (32, 4, 128, (1, 1020, 1024, 1025, 2049), None, None),
        # the window's first live page inside a group (pages 25, 75,
        # 112), on a group's first (64), a context shorter than the window
        "g8-window": (32, 4, 128, (1, 700, 1500, 2100, 1324, 299), 300,
                      None),
        # the deepest slot ends on a group's last page, on the next one's
        # first, on the third one's first
        "g4-t3": (32, 8, 64, (1, 1022, 1023, 2047), None, 3),
    }

    @pytest.mark.parametrize("layout", list(CELL_LAYOUTS))
    def test_parity_at_a_cells_layout(self, layout):
        import jax.numpy as jnp
        from paddle_tpu.incubate.nn.pallas import paged_attention as pa

        hq, hkv, d, lens, window, t = self.CELL_LAYOUTS[layout]
        assert pa._pages_per_group(self.BS, hkv * d * 2) == 64
        rng = np.random.RandomState(7)
        b, bf16 = len(lens), jnp.bfloat16
        used = [-(-(n + (t or 1) - 1) // self.BS) for n in lens]
        tables = np.zeros((b, max(used) + 2), np.int32)
        ids = 1 + rng.permutation(sum(used))
        for i, u in enumerate(used):
            tables[i, :u] = ids[sum(used[:i]):sum(used[:i]) + u]
        n = sum(used) + 1
        q = jnp.asarray(rng.randn(*((b, hq, d) if t is None
                                    else (b, t, hq, d))), bf16)
        kp = jnp.asarray(rng.randn(n, self.BS, hkv, d), bf16)
        vp = jnp.asarray(rng.randn(n, self.BS, hkv, d), bf16)
        bt, cl = jnp.asarray(tables), jnp.asarray(np.array(lens, np.int32))
        if t is None:
            out = pa.paged_attention(q, kp, vp, bt, cl, sm_scale=0.1,
                                     interpret=True, window=window)
            want = pa.paged_attention_reference(q, kp, vp, bt, cl,
                                                sm_scale=0.1, window=window)
        else:
            out = pa.paged_attention_multi(q, kp, vp, bt, cl, sm_scale=0.1,
                                           interpret=True)
            rep = lambda p: jnp.repeat(p, hq // hkv, axis=2)  # noqa: E731
            want = pa.paged_attention_multi_reference(
                q, rep(kp), rep(vp), bt, cl, sm_scale=0.1)
        assert out.dtype == bf16 and out.shape == q.shape
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            rtol=0.05, atol=0.05)

    def test_counts_its_calls_by_rows(self, small_groups):
        """`kernels/paged/rows_<R>`: one count a call while a program is
        traced, by its group's rows."""
        import jax
        from paddle_tpu.core.monitor import stat_get
        from paddle_tpu.incubate.nn.pallas import paged_attention as pa

        q, kp, vp, bt, cl = self._inputs("float32")
        before = stat_get("kernels/paged/rows_128")
        fn = jax.jit(lambda *a: pa.paged_attention(*a, interpret=True))
        fn(q, kp, vp, bt, cl)
        fn(q, kp, vp, bt, cl)          # a cached program: not traced again
        assert stat_get("kernels/paged/rows_128") - before == 1

    @pytest.mark.parametrize("dtype,tol", [("float32", 2e-6),
                                           ("bfloat16", 0.05)])
    @pytest.mark.parametrize("t", [None, 1, 3])
    def test_parity_at_every_edge(self, small_groups, dtype, tol, t):
        from paddle_tpu.incubate.nn.pallas import paged_attention as pa

        q, kp, vp, bt, cl = self._inputs(dtype, t)
        kernel, ref = (
            (pa.paged_attention, pa.paged_attention_reference)
            if t is None else
            (pa.paged_attention_multi, pa.paged_attention_multi_reference))
        if t is not None:
            # the deepest slot of a full table would read past it
            cl = cl - (t - 1) * (cl + t - 1 > self.MAXB * self.BS)
        out = kernel(q, kp, vp, bt, cl, sm_scale=0.2, interpret=True)
        want = ref(q, kp, vp, bt, cl, sm_scale=0.2)
        assert out.dtype == q.dtype and out.shape == q.shape
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_dead_pages_and_null_block_never_count(self, small_groups,
                                                   dtype):
        """Pages past the context inside a live group, whole dead
        groups, the padded table columns and the NULL block they all
        point at: poisoned, the output is the same to the bit."""
        import jax.numpy as jnp
        from paddle_tpu.incubate.nn.pallas import paged_attention as pa

        q, kp, vp, bt, cl = self._inputs(dtype)
        out = pa.paged_attention(q, kp, vp, bt, cl, sm_scale=0.3,
                                 interpret=True)
        live = np.zeros((self.N, self.BS), bool)
        bt_np = np.asarray(bt)
        for b, n in enumerate(self.LENS):
            for pos in range(n):
                live[bt_np[b, pos // self.BS], pos % self.BS] = True
        assert not live[0].any()            # the NULL block
        mask = jnp.asarray(live)[:, :, None, None]
        out2 = pa.paged_attention(
            q, jnp.where(mask, kp, 1e9).astype(kp.dtype),
            jnp.where(mask, vp, -1e9).astype(vp.dtype), bt, cl,
            sm_scale=0.3, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out, np.float32), np.asarray(out2, np.float32))

    def test_slot0_of_a_window_is_the_decode_kernel(self, small_groups):
        from paddle_tpu.incubate.nn.pallas import paged_attention as pa

        q, kp, vp, bt, cl = self._inputs("float32", t=3)
        cl = cl - 2 * (cl + 2 > self.MAXB * self.BS)
        multi = pa.paged_attention_multi(q, kp, vp, bt, cl,
                                         sm_scale=0.3, interpret=True)
        single = pa.paged_attention(q[:, 0], kp, vp, bt, cl,
                                    sm_scale=0.3, interpret=True)
        np.testing.assert_allclose(np.asarray(multi[:, 0]),
                                   np.asarray(single),
                                   rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# ISSUE 30: the kernel is chosen from platform, mesh and shape
# ---------------------------------------------------------------------------

class TestPagedKernelSelection:
    @pytest.fixture(autouse=True)
    def _no_switches(self, monkeypatch):
        monkeypatch.delenv("PADDLE_PALLAS_FUSION", raising=False)
        monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)

    @staticmethod
    def _runner(hidden=64, heads=4):
        from paddle_tpu.inference.serving.model_runner import GPT2Runner

        return GPT2Runner(tiny_model(hidden=hidden, heads=heads))

    def test_cpu_takes_the_kernel_only_through_the_interpreter(
            self, monkeypatch):
        runner = self._runner()
        assert runner.kernel_supported(8) is False
        monkeypatch.setenv("PADDLE_PALLAS_FUSION", "1")
        assert runner.kernel_supported(8) is False
        monkeypatch.delenv("PADDLE_PALLAS_FUSION")
        monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
        assert runner.kernel_supported(8) is True
        # the interpreter takes any shape (odd-shape parity tests)
        assert runner.kernel_supported(4) is True

    @pytest.mark.parametrize("heads,head_dim,block,mesh_size,want", [
        (16, 64, 16, 1, True),      # the cell's
        (12, 64, 16, 1, True),      # GPT-2 small: 768 lanes
        (3, 64, 16, 1, False),      # H*D = 192: not whole 128-lane rows
        (16, 64, 4, 1, False),      # a block under one sublane group
        (16, 64, 16, 4, False),     # a live multi-device mesh
    ])
    def test_on_a_tpu_shape_and_mesh_decide(self, monkeypatch, heads,
                                            head_dim, block, mesh_size,
                                            want):
        import types

        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.incubate.nn import pallas
        from paddle_tpu.incubate.nn.pallas import paged_attention as pa

        monkeypatch.setattr(pallas, "_on_tpu", lambda: True)
        monkeypatch.setattr(
            mesh_mod, "get_mesh",
            lambda: types.SimpleNamespace(size=mesh_size))
        assert pa.paged_decode_supported(heads, head_dim, block) is want
        # the switch of the LayerNorm and optimizer kernels is not asked
        monkeypatch.setenv("PADDLE_PALLAS_FUSION", "1")
        assert pa.paged_decode_supported(heads, head_dim, block) is want

    def test_live_mesh_on_the_cpu_devices(self, monkeypatch):
        """`_partitioned()` over a real mesh of the 8 virtual devices,
        and over none."""
        import jax

        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.incubate.nn import pallas
        from paddle_tpu.incubate.nn.pallas import paged_attention as pa

        monkeypatch.setattr(pallas, "_on_tpu", lambda: True)
        before = mesh_mod.get_mesh()
        try:
            mesh_mod.set_mesh(mesh_mod.build_mesh(
                {"dp": len(jax.devices())}))
            assert pa.paged_decode_supported(16, 64, 16) is False
            mesh_mod.set_mesh(None)
            assert pa.paged_decode_supported(16, 64, 16) is True
        finally:
            mesh_mod.set_mesh(before)

    @staticmethod
    def _latent_runner(row):
        """The runner of a model with latent rows as far as
        `kernel_supported` reads it: one head as wide as the stored
        row."""
        from paddle_tpu.inference.serving.state_runner import StateRunner

        runner = object.__new__(StateRunner)
        runner.heads = (1, 1, row)
        return runner

    def test_latent_runner_on_the_cpu_takes_the_interpreter_only(
            self, monkeypatch):
        runner = self._latent_runner(640)
        assert runner.kernel_supported(16) is False
        monkeypatch.setenv("PADDLE_PALLAS_FUSION", "1")
        assert runner.kernel_supported(16) is False
        monkeypatch.delenv("PADDLE_PALLAS_FUSION")
        monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
        assert runner.kernel_supported(16) is True
        assert runner.kernel_supported(4) is True

    @pytest.mark.parametrize("row,block,mesh_size,want", [
        (640, 16, 1, True),         # both MLA cells: 576 stored in 640
        (128, 8, 1, True),
        (576, 16, 1, False),        # the row as it is: not whole lanes
        (640, 4, 1, False),         # a block under one sublane group
        (640, 16, 4, False),        # a live multi-device mesh
    ])
    def test_latent_runner_on_a_tpu_shape_and_mesh_decide(
            self, monkeypatch, row, block, mesh_size, want):
        import types

        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.incubate.nn import pallas

        monkeypatch.setattr(pallas, "_on_tpu", lambda: True)
        monkeypatch.setattr(
            mesh_mod, "get_mesh",
            lambda: types.SimpleNamespace(size=mesh_size))
        assert self._latent_runner(row).kernel_supported(block) is want

    def test_latent_runner_stores_whole_lane_rows(self):
        """What the runner hands the predicate is the padded row:
        a model's 40 values a token are stored in 128."""
        from paddle_tpu.inference.serving import model_runner as mr
        from paddle_tpu.text.models import glm4_moe_lite as glm

        cfg = glm.Glm4MoeLiteConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, q_lora_rank=8, kv_lora_rank=32,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            intermediate_size=32, moe_intermediate_size=16,
            n_routed_experts=4, num_experts_per_tok=2,
            max_position_embeddings=32)
        runner = mr.runner_for(glm.Glm4MoeLiteForCausalLM(cfg))
        assert cfg.latent_row == 40 and runner.pool_rows == (128,)

    def test_layernorm_and_optimizer_kernels_keep_their_switch(
            self, monkeypatch):
        from paddle_tpu.incubate.nn import pallas

        monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
        assert not pallas.kernels_available()
        assert not pallas.ln_supported(1024)
        assert not pallas.optim_supported()
        monkeypatch.setenv("PADDLE_PALLAS_FUSION", "1")
        assert pallas.ln_supported(1024) and pallas.optim_supported()
        monkeypatch.setattr(pallas, "_on_tpu", lambda: True)
        monkeypatch.delenv("PADDLE_PALLAS_FUSION")
        assert not pallas.ln_supported(1024)
        assert not pallas.optim_supported()

    def test_engine_counts_the_dispatches_that_attend_paged(
            self, monkeypatch):
        """Through the interpreter the engine emits the dense
        engine's tokens and every target dispatch counts as paged; a
        dense engine grows `serve/attn/steps` alone."""
        model = tiny_model()
        prompts = [[4, 5, 6, 7], [9, 10]]
        sp = SamplingParams(max_new_tokens=5)

        def run(**kw):
            eng = LLMEngine(model, max_batch=2, block_size=8,
                            num_blocks=32, **kw)
            out, deltas = _counter_deltas(
                ("serve/attn/",),
                lambda: eng.generate(prompts, sampling=sp))
            return eng, out, deltas

        dense, want, deltas = run()
        assert not dense.use_kernel
        assert deltas == {"serve/attn/steps": 4}
        monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
        paged, got, deltas = run()
        assert paged.use_kernel and paged._kernel_interpret
        assert got == want
        assert deltas == {"serve/attn/steps": 4,
                          "serve/attn/steps_paged": 4}
        forced, got, deltas = run(use_kernel=False)
        assert got == want and deltas == {"serve/attn/steps": 4}

    def test_verify_dispatches_count_too(self, monkeypatch):
        model = tiny_model()
        sp = SamplingParams(max_new_tokens=6)
        want = LLMEngine(model, max_batch=2, block_size=8,
                         num_blocks=32).generate([[5, 6, 7]], sampling=sp)
        monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
        eng = LLMEngine(model, max_batch=2, block_size=8, num_blocks=32,
                        spec_k=3)
        got, deltas = _counter_deltas(
            ("serve/attn/",),
            lambda: eng.generate([[5, 6, 7]], sampling=sp))
        assert got == want
        assert deltas["serve/attn/steps"] > 0
        assert deltas["serve/attn/steps_paged"] \
            == deltas["serve/attn/steps"]


# ---------------------------------------------------------------------------
# engine e2e
# ---------------------------------------------------------------------------

class TestEngineE2E:
    def test_concurrent_mixed_lengths_bit_identical_greedy(self):
        """THE acceptance: 8 concurrent requests of different lengths
        through continuous batching produce exactly the tokens the
        sequential unbatched full-re-forward loop produces, and the
        pool drains to zero used blocks."""
        model = tiny_model()
        eng = LLMEngine(model, max_batch=8, block_size=8,
                        num_blocks=64)
        rng = np.random.RandomState(1)
        lens = (1, 3, 8, 9, 13, 17, 24, 5)
        prompts = [list(rng.randint(1, 128, n)) for n in lens]
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=8))
                for p in prompts]
        eng.step()
        assert len(eng.scheduler.running) == 8  # truly concurrent
        while eng.has_unfinished():
            eng.step()
        outs = [eng.get_request(i).output_ids for i in reqs]
        refs = [ref_greedy(model, p, 8) for p in prompts]
        assert outs == refs
        assert eng.check_drained() == {}
        assert eng.cache.allocator.used_blocks == 0

    def test_generate_and_telemetry(self):
        model = tiny_model()
        before_req = cmon.stat_get("serve/requests")
        before_tok = cmon.stat_get("serve/tokens")
        eng = LLMEngine(model, max_batch=4, block_size=8,
                        num_blocks=32)
        outs = eng.generate([[5, 6, 7], [9]],
                            sampling=SamplingParams(max_new_tokens=4))
        assert [len(o) for o in outs] == [4, 4]
        assert cmon.stat_get("serve/requests") == before_req + 2
        assert cmon.stat_get("serve/tokens") == before_tok + 8
        assert cmon.stat_get("serve/prefill_us") > 0
        assert cmon.stat_get("serve/decode_us") > 0

    def test_streaming_callback_order(self):
        model = tiny_model()
        eng = LLMEngine(model, max_batch=2, block_size=8,
                        num_blocks=32)
        seen = []
        rid = eng.add_request(
            [3, 1, 4], SamplingParams(max_new_tokens=5),
            on_token=lambda r, t: seen.append((r, t)))
        while eng.has_unfinished():
            eng.step()
        req = eng.get_request(rid)
        assert [t for _, t in seen] == req.output_ids
        assert all(r == rid for r, _ in seen)

    def test_eviction_replay_matches_uninterrupted(self):
        """A pool too small for the whole load forces mid-decode
        evictions; recompute-from-prompt+output must land on exactly
        the tokens an uninterrupted run produces."""
        model = tiny_model()
        prompts = [[7, 8, 9, 10], [20, 21], [30, 31, 32], [40]]
        sp = SamplingParams(max_new_tokens=10)
        big = LLMEngine(model, max_batch=4, block_size=4,
                        num_blocks=64)
        want = big.generate(prompts, sampling=sp)
        small = LLMEngine(model, max_batch=4, block_size=4,
                          num_blocks=9)  # 8 usable: forces evictions
        got = small.generate(prompts, sampling=sp)
        assert got == want
        assert cmon.stat_get("serve/evictions") > 0
        assert small.check_drained() == {}

    def test_temperature_sampling_deterministic_and_per_request(self):
        model = tiny_model()

        def run():
            eng = LLMEngine(model, max_batch=4, block_size=8,
                            num_blocks=32)
            a = eng.add_request([5, 6], SamplingParams(
                max_new_tokens=6, temperature=1.0, seed=7))
            b = eng.add_request([5, 6], SamplingParams(
                max_new_tokens=6, temperature=1.0, top_k=4, seed=8))
            g = eng.add_request([5, 6], SamplingParams(
                max_new_tokens=6))  # greedy rides the same batch
            while eng.has_unfinished():
                eng.step()
            return [eng.get_request(i).output_ids for i in (a, b, g)]

        first, second = run(), run()
        assert first == second              # seeded determinism
        assert first[0] != first[1]         # per-request streams
        assert first[2] == ref_greedy(model, [5, 6], 6)

    def test_stop_conditions(self):
        model = tiny_model()
        eng = LLMEngine(model, max_batch=2, block_size=8,
                        num_blocks=32)
        probe = eng.generate([[11, 12, 13]],
                             sampling=SamplingParams(
                                 max_new_tokens=6))[0]
        eos = probe[2]  # third generated token
        eng2 = LLMEngine(model, max_batch=2, block_size=8,
                         num_blocks=32)
        out = eng2.generate([[11, 12, 13]],
                            sampling=SamplingParams(
                                max_new_tokens=6,
                                eos_token_id=eos))[0]
        assert out == probe[:3]  # stopped AT the eos token
        assert eng2.check_drained() == {}

    def test_max_seq_len_cap(self):
        model = tiny_model(seq=32)
        eng = LLMEngine(model, max_batch=1, block_size=8,
                        num_blocks=16)
        out = eng.generate([[1] * 28],
                           sampling=SamplingParams(
                               max_new_tokens=50))[0]
        assert len(out) == 4  # capped at max_seq_len=32
        assert eng.check_drained() == {}

    def test_finished_request_retention_bounded(self):
        """A long-lived replica must not grow host memory with total
        traffic: finished records are capped (generate() releases
        its own as results are returned)."""
        model = tiny_model()
        eng = LLMEngine(model, max_batch=2, block_size=8,
                        num_blocks=32)
        eng._keep_finished = 3
        for _ in range(6):
            eng.add_request([5, 6], SamplingParams(max_new_tokens=1))
            while eng.has_unfinished():
                eng.step()
        assert len(eng._requests) <= 4  # 3 kept + the newest
        out = eng.generate([[7]], sampling=SamplingParams(
            max_new_tokens=1))[0]
        assert len(out) == 1   # generate still works...
        # ...and released its own record as results were returned
        assert all(r.finished for r in eng._requests.values())

    def test_pool_too_small_is_loud(self):
        model = tiny_model()
        eng = LLMEngine(model, max_batch=1, block_size=4,
                        num_blocks=3)  # 2 usable blocks
        eng.add_request([1] * 12, SamplingParams(max_new_tokens=2))
        with pytest.raises(RuntimeError, match="pool"):
            while eng.has_unfinished():
                eng.step()

    def test_decode_matches_kernel_interpret_path(self):
        """The engine's dense fallback and the Pallas interpret-mode
        kernel path agree on tokens end to end."""
        model = tiny_model()
        prompts = [[4, 5, 6, 7], [9, 10]]
        sp = SamplingParams(max_new_tokens=6)
        dense = LLMEngine(model, max_batch=2, block_size=8,
                          num_blocks=32, use_kernel=False)
        want = dense.generate(prompts, sampling=sp)
        os.environ["PADDLE_PALLAS_INTERPRET"] = "1"
        try:
            kern = LLMEngine(model, max_batch=2, block_size=8,
                             num_blocks=32)
            assert kern.use_kernel
            got = kern.generate(prompts, sampling=sp)
        finally:
            os.environ.pop("PADDLE_PALLAS_INTERPRET", None)
        assert got == want


# ---------------------------------------------------------------------------
# chaos: serve_admit / serve_decode
# ---------------------------------------------------------------------------

class TestServingChaos:
    def test_sites_registered(self):
        assert "serve_admit" in chaos.SITES
        assert "serve_decode" in chaos.SITES

    def test_admit_fault_leaves_queue_intact(self):
        """A raising admission fault (slow-client teardown analog)
        fires BEFORE the request takes pool resources: the step
        raises, nothing leaks, the retry admits normally."""
        model = tiny_model()
        eng = LLMEngine(model, max_batch=2, block_size=8,
                        num_blocks=32)
        eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=3))
        with chaos.inject("serve_admit", "raise", times=1) as rule:
            with pytest.raises(chaos.ChaosInjected):
                eng.step()
            assert rule.triggers == 1
            assert eng.cache.allocator.used_blocks == 0
            while eng.has_unfinished():
                eng.step()
        assert eng.check_drained() == {}

    def test_slow_client_admission_delay(self):
        model = tiny_model()
        eng = LLMEngine(model, max_batch=2, block_size=8,
                        num_blocks=32)
        before = cmon.stat_get("chaos/serve_admit/delay/triggered")
        with chaos.inject("serve_admit", "delay", ms=1):
            out = eng.generate([[5, 6]], sampling=SamplingParams(
                max_new_tokens=2))
        assert len(out[0]) == 2
        assert cmon.stat_get(
            "chaos/serve_admit/delay/triggered") == before + 1

    def test_admit_fault_mid_pass_keeps_earlier_admissions(self):
        """A raise at the serve_admit site for request N+1 must not
        strand request N admitted-but-never-prefilled (its decode
        would read never-written K/V): admissions prefill one by one,
        so everything admitted before the fault already has its K/V
        and first token."""
        model = tiny_model()
        sp = SamplingParams(max_new_tokens=4)
        clean = LLMEngine(model, max_batch=4, block_size=8,
                          num_blocks=32)
        want = clean.generate([[3, 4, 5], [6, 7]], sampling=sp)
        eng = LLMEngine(model, max_batch=4, block_size=8,
                        num_blocks=32)
        a = eng.add_request([3, 4, 5], sp)
        b = eng.add_request([6, 7], sp)
        with chaos.inject("serve_admit", "raise", after=1,
                          times=1):
            with pytest.raises(chaos.ChaosInjected):
                eng.step()
        assert len(eng.get_request(a).output_ids) == 1  # prefilled
        assert eng.get_request(b).state == WAITING      # untouched
        while eng.has_unfinished():
            eng.step()
        assert [eng.get_request(i).output_ids
                for i in (a, b)] == want
        assert eng.check_drained() == {}

    def test_persistent_oom_raises_instead_of_spinning(self):
        """An OOM that never goes away must escalate after a bounded
        number of consecutive failed dispatches — not spin on
        evict/readmit forever."""
        model = tiny_model()
        eng = LLMEngine(model, max_batch=2, block_size=8,
                        num_blocks=32)
        eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=8))
        with chaos.inject("serve_decode", "resource_exhausted"):
            with pytest.raises(chaos.XlaRuntimeError):
                for _ in range(50):
                    eng.step()
                    if not eng.has_unfinished():
                        break
        assert eng.check_drained() == {}

    def test_donated_pool_loss_resets_and_replays(self, monkeypatch):
        """A real RESOURCE_EXHAUSTED during the DONATED decode
        dispatch deletes the pools mid-flight; the engine must
        detect it, rebuild the pools, and replay every running
        request to the exact fault-free tokens — never re-dispatch
        the deleted buffers (the PTA041 class)."""
        model = tiny_model()
        sp = SamplingParams(max_new_tokens=6)
        prompts = [[4, 5, 6], [7, 8]]
        clean = LLMEngine(model, max_batch=2, block_size=8,
                          num_blocks=32)
        want = clean.generate(prompts, sampling=sp)
        eng = LLMEngine(model, max_batch=2, block_size=8,
                        num_blocks=32)
        ids = [eng.add_request(p, sp) for p in prompts]
        eng.step()  # prefill both + one clean decode
        orig = eng._enqueue_decode
        state = {"fired": False}

        def boom(*arrays):
            if not state["fired"]:
                state["fired"] = True
                eng.cache.k.delete()   # donation consumed the pools
                eng.cache.v.delete()
                raise chaos.XlaRuntimeError(
                    "RESOURCE_EXHAUSTED: out of memory (test)")
            return orig(*arrays)

        monkeypatch.setattr(eng, "_enqueue_decode", boom)
        before = cmon.stat_get("serve/pool_resets")
        while eng.has_unfinished():
            eng.step()
        assert cmon.stat_get("serve/pool_resets") == before + 1
        assert [eng.get_request(i).output_ids for i in ids] == want
        assert eng.check_drained() == {}

    def test_flood_with_injected_oom_survives_without_leaks(self):
        """THE chaos regression: a request flood with synthetic
        RESOURCE_EXHAUSTED injected mid-decode — the scheduler evicts
        and recovers, every request completes with the fault-free
        tokens, and the pool drains leak-free."""
        model = tiny_model()
        rng = np.random.RandomState(3)
        prompts = [list(rng.randint(1, 128, n))
                   for n in (3, 9, 5, 12, 7, 4, 10, 6, 8, 2)]
        sp = SamplingParams(max_new_tokens=6)
        clean = LLMEngine(model, max_batch=4, block_size=8,
                          num_blocks=32)
        want = clean.generate(prompts, sampling=sp)
        eng = LLMEngine(model, max_batch=4, block_size=8,
                        num_blocks=32)
        before = cmon.stat_get("serve/oom_evictions")
        with chaos.inject("serve_decode", "resource_exhausted",
                          after=2, every=4, times=3) as rule:
            got = eng.generate(prompts, sampling=sp)
            assert rule.triggers == 3
        assert got == want
        assert cmon.stat_get("serve/oom_evictions") >= before + 3
        assert eng.check_drained() == {}
        assert eng.cache.allocator.used_blocks == 0


# ---------------------------------------------------------------------------
# PTA07x: KV block-leak sanitizer
# ---------------------------------------------------------------------------

class TestPTA07x:
    def test_runtime_leak_detection(self):
        msan.configure("serving")
        try:
            msan.clear_findings()
            a = BlockAllocator(8)
            a.alloc("ghost", 3)
            before = cmon.stat_get("analysis/PTA070/findings")
            leaked = a.audit_leaks(live_owners=())
            assert leaked == {"ghost": a.owned("ghost")}
            assert cmon.stat_get(
                "analysis/PTA070/findings") == before + 1
            codes = [f.code for f in msan.findings()]
            assert "PTA070" in codes
        finally:
            msan.disarm()
            msan.clear_findings()

    def test_runtime_double_free_finding(self):
        msan.configure("serving")
        try:
            msan.clear_findings()
            a = BlockAllocator(8)
            got = a.alloc("r", 2)
            a.free_one("r", got[0])
            before = cmon.stat_get("analysis/PTA071/findings")
            with pytest.raises(ValueError):
                a.free_one("r", got[0])
            assert cmon.stat_get(
                "analysis/PTA071/findings") == before + 1
        finally:
            msan.disarm()
            msan.clear_findings()

    def test_disarmed_is_silent(self):
        assert not msan.armed("serving")
        a = BlockAllocator(8)
        a.alloc("ghost", 2)
        before = cmon.stat_get("analysis/PTA070/findings")
        assert a.audit_leaks(()) == {"ghost": a.owned("ghost")}
        assert cmon.stat_get("analysis/PTA070/findings") == before

    def test_engine_drain_audit_reports_live_requests_only(self):
        model = tiny_model()
        eng = LLMEngine(model, max_batch=2, block_size=8,
                        num_blocks=32)
        eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=4))
        eng.step()  # running mid-generation: owned but NOT a leak
        assert eng.check_drained() == {}
        while eng.has_unfinished():
            eng.step()
        assert eng.check_drained() == {}

    def test_static_lint_discarded_alloc(self):
        from paddle_tpu.analysis.serving import lint_kv_source

        src = ("def admit(a, req):\n"
               "    a.alloc(req, 3)\n"
               "    return req\n")
        rep = lint_kv_source(src, filename="x.py")
        assert [f.code for f in rep.findings] == ["PTA070"]

    def test_static_lint_drop_without_release(self):
        from paddle_tpu.analysis.serving import lint_kv_source

        bad = ("def drop(self, slot):\n"
               "    req = self.running.pop(slot)\n"
               "    return req\n")
        rep = lint_kv_source(bad, filename="x.py")
        assert [f.code for f in rep.findings] == ["PTA072"]
        good = ("def drop(self, slot):\n"
                "    req = self.running.pop(slot)\n"
                "    self.cache.allocator.release(req.req_id)\n")
        assert lint_kv_source(good, filename="x.py").findings == []

    def test_static_lint_clean_over_serving_sources(self):
        """The serving engine itself must satisfy its own lint —
        every request-drop path releases."""
        from paddle_tpu.analysis.cli import iter_target_files, \
            lint_file
        from paddle_tpu.analysis.diagnostics import Report

        rep = Report()
        target = os.path.join(REPO, "paddle_tpu", "inference",
                              "serving")
        for path in iter_target_files(target):
            lint_file(path, rep, sanitize=("serving",))
        assert not rep.findings, [f.format() for f in rep.findings]

    def test_audit_block_accounting_report(self):
        from paddle_tpu.analysis.serving import audit_block_accounting

        a = BlockAllocator(8)
        a.alloc("dead", 2)
        a.alloc("live", 1)
        rep = audit_block_accounting(a, live_owners=("live",),
                                     where="test")
        assert [f.code for f in rep.findings] == ["PTA070"]
        assert "dead" in rep.findings[0].message

    def test_cli_serving_family_wired(self):
        from paddle_tpu.analysis.cli import SANITIZE_FAMILIES

        assert "serving" in SANITIZE_FAMILIES

    def test_sanitize_family_grammar(self):
        fams = msan.parse_spec("serving")
        assert "serving" in fams
        assert "serving" in msan.FAMILIES


# ---------------------------------------------------------------------------
# doc drift: README covers the serving surface
# ---------------------------------------------------------------------------

_ENV_RE = re.compile(r"PADDLE_SERVE_[A-Z_]+")


class TestServingDocDrift:
    def _readme(self):
        with open(os.path.join(REPO, "README.md")) as f:
            return f.read()

    def test_env_vars_documented(self):
        """Every PADDLE_SERVE_* knob in inference/serving/ source is
        in the README env table."""
        srcdir = os.path.join(REPO, "paddle_tpu", "inference",
                              "serving")
        used = set()
        for name in os.listdir(srcdir):
            if name.endswith(".py"):
                with open(os.path.join(srcdir, name)) as f:
                    used |= set(_ENV_RE.findall(f.read()))
        assert used  # the knobs exist
        doc = self._readme()
        missing = sorted(v for v in used if v not in doc)
        assert not missing, (
            f"serving env vars missing from README: {missing}")

    def test_serving_section_and_codes(self):
        doc = self._readme()
        assert "## Serving" in doc
        for code in ("PTA070", "PTA071", "PTA072", "PTA073",
                     "PTA074"):
            assert code in doc, f"{code} missing from README"
        for site in ("serve_admit", "serve_decode", "serve_route",
                     "serve_drain", "serve_spec_verify"):
            assert site in doc, f"chaos site {site} undocumented"

    def test_spec_and_prefix_sections(self):
        """ISSUE-19 satellite: the README documents the speculative-
        decoding + prefix-caching surface — knobs, counters, chaos
        site, sanitizer code, bench twin."""
        doc = self._readme()
        assert "Speculative decoding" in doc
        assert "Prefix caching" in doc
        for word in ("serve/spec/", "serve/hist/accept_len",
                     "serve/prefix/prefill_tokens_saved",
                     "copy-on-write", "check_cow",
                     "extra.serve_spec", "spec_k", "prefix_cache"):
            assert word in doc, f"{word!r} missing from README"

    def test_resilience_section(self):
        """ISSUE-13 satellite: the README documents the resilience
        surface — deadline/shed/drain/router API and counters."""
        doc = self._readme()
        assert "Serving resilience" in doc
        for word in ("Router", "drain(", "EngineOverloaded",
                     "EngineTimeout", "deadline_s", "priority",
                     "serve/failovers", "serve/shed",
                     "serve/deadline_aborts", "serve/drains",
                     "import_request"):
            assert word in doc, f"{word!r} missing from README"
        assert "LLMEngine" in doc


# ---------------------------------------------------------------------------
# ISSUE 19: prefix-cache refcounts + copy-on-write (allocator/cache)
# ---------------------------------------------------------------------------

class TestRefcountsAndPrefixIndex:
    def test_double_share_then_single_free(self):
        a = BlockAllocator(8)
        (b0,) = a.alloc("a", 1)
        a.share("x", b0)
        a.share("y", b0)
        assert a.refcount(b0) == 3
        # dropping one reference must NOT reclaim the block
        assert a.release("a") == 1
        assert a.refcount(b0) == 2 and a.free_blocks == 6
        a.free_one("x", b0)
        assert a.refcount(b0) == 1 and a.free_blocks == 6
        a.release("y")  # last reference: now it really frees
        assert a.refcount(b0) == 0 and a.free_blocks == 7

    def test_share_unallocated_or_null_raises(self):
        a = BlockAllocator(8)
        with pytest.raises(ValueError):
            a.share("x", NULL_BLOCK)
        with pytest.raises(ValueError):
            a.share("x", 5)  # never allocated

    def test_check_cow_blocks_shared_writes(self):
        a = BlockAllocator(8)
        (b0,) = a.alloc("a", 1)
        assert a.check_cow(b0) == b0  # sole owner: writable
        a.share("b", b0)
        with pytest.raises(ValueError):
            a.check_cow(b0)  # shared: immutable

    def test_eviction_of_sharer_never_reclaims_shared_blocks(self):
        c = PagedKVCache(1, 2, 8, block_size=4, num_blocks=10,
                         prefix_cache=True)
        toks = list(range(1, 10))  # 2 full blocks + 1 tail token
        assert c.admit("r1", toks) == 0  # cold cache
        c.register_prefix("r1", toks)
        assert c.admit("r2", toks) == 8  # shares the 2 full blocks
        shared = c.allocator.owned("r2")[:2]
        assert shared == c.allocator.owned("r1")[:2]
        free_before = c.allocator.free_blocks
        c.allocator.release("r2")  # evict the sharer
        # only r2's PRIVATE tail block returned; the shared pair stays
        assert c.allocator.free_blocks == free_before + 1
        for b in shared:
            assert c.allocator.refcount(b) == 1
        assert c.allocator.owned("r1")[:2] == shared

    def test_can_admit_accounts_cached_blocks(self):
        c = PagedKVCache(1, 2, 8, block_size=8, num_blocks=6)
        # 5 usable blocks: a 5-block prompt + 1 lookahead won't fit...
        assert not c.can_admit(8 * 5)
        # ...unless 2 of its blocks are already cached
        assert c.can_admit(8 * 5, cached_blocks=2)
        # k-aware decode lookahead eats into the same budget
        assert c.can_admit(8 * 2, lookahead_blocks=3)
        assert not c.can_admit(8 * 2, lookahead_blocks=4)

    def test_last_free_deregisters_hash(self):
        c = PagedKVCache(1, 2, 8, block_size=4, num_blocks=8,
                         prefix_cache=True)
        toks = list(range(1, 10))
        c.admit("r1", toks)
        c.register_prefix("r1", toks)
        digs = list(c.allocator._by_hash)
        assert len(digs) == 2
        c.allocator.release("r1")
        for d in digs:
            assert c.allocator.lookup_hash(d) is None
        assert c.admit("r2", toks) == 0  # cold again, no stale hit

    def test_defrag_preserves_both_sharers_tables(self):
        import jax.numpy as jnp

        c = PagedKVCache(1, 2, 4, block_size=2, num_blocks=12,
                         prefix_cache=True)
        c.allocator.alloc("hole", 3)
        toks = [5, 6, 7, 8, 9]  # 2 full blocks + 1 tail token
        assert c.admit("a", toks) == 0
        c.register_prefix("a", toks)
        assert c.admit("b", toks) == 4
        c.allocator.release("hole")  # holes at the front
        # stamp each block with its id so moves are detectable
        c.k = jnp.arange(c.num_blocks, dtype=c.k.dtype).reshape(
            1, -1, 1, 1) * jnp.ones_like(c.k)
        a_before = c.allocator.owned("a")
        b_before = c.allocator.owned("b")
        stamps = {blk: float(c.k[0, blk, 0, 0])
                  for blk in set(a_before + b_before)}
        digest_of = dict(c.allocator._hash_of)
        assert c.defrag() > 0
        a_after = c.allocator.owned("a")
        b_after = c.allocator.owned("b")
        # the shared leading pair moved ONCE and leads BOTH tables
        assert a_after[:2] == b_after[:2]
        assert a_after[2] != b_after[2]  # private tails stay private
        for old, new in zip(a_before, a_after):
            assert float(c.k[0, new, 0, 0]) == stamps[old]
        for old, new in zip(b_before, b_after):
            assert float(c.k[0, new, 0, 0]) == stamps[old]
        # refcounts and the content-hash index moved with the blocks
        for blk in a_after[:2]:
            assert c.allocator.refcount(blk) == 2
        remap = dict(zip(a_before, a_after))
        for old, dig in digest_of.items():
            assert c.allocator.lookup_hash(dig) == remap[old]
        # a third admission still shares post-defrag
        assert c.admit("c2", toks) == 4


# ---------------------------------------------------------------------------
# ISSUE 19: multi-query verify kernel
# ---------------------------------------------------------------------------

class TestMultiQueryKernel:
    def _rand(self, b=4, t=8, h=4, d=32, bs=8, n=24, maxb=6):
        import jax.numpy as jnp

        rng = np.random.RandomState(7)
        q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
        kp = jnp.asarray(rng.randn(n, bs, h, d), jnp.float32)
        vp = jnp.asarray(rng.randn(n, bs, h, d), jnp.float32)
        bt = jnp.asarray(rng.randint(1, n, (b, maxb)), jnp.int32)
        return q, kp, vp, bt

    @pytest.mark.parametrize("t,lens", [
        (2, (1, 8, 9, 15)),    # around block boundaries
        (4, (8, 16, 3, 23)),
        (8, (1, 5, 17, 33)),   # widest supported window
    ])
    def test_interpret_parity_vs_dense(self, t, lens):
        import jax.numpy as jnp
        from paddle_tpu.incubate.nn.pallas.paged_attention import (
            paged_attention_multi, paged_attention_multi_reference)

        q, kp, vp, bt = self._rand(t=t)
        cl = jnp.asarray(np.array(lens, np.int32))
        out = paged_attention_multi(q, kp, vp, bt, cl, sm_scale=0.2,
                                    interpret=True)
        ref = paged_attention_multi_reference(q, kp, vp, bt, cl,
                                              sm_scale=0.2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-6, atol=2e-6)

    def test_window_too_wide_rejected(self):
        import jax.numpy as jnp
        from paddle_tpu.incubate.nn.pallas.paged_attention import (
            paged_attention_multi)

        q, kp, vp, bt = self._rand(t=9)
        cl = jnp.asarray(np.array([1, 2, 3, 4], np.int32))
        with pytest.raises(ValueError):
            paged_attention_multi(q, kp, vp, bt, cl, interpret=True)

    def test_slot0_matches_single_query_kernel(self):
        """A 1-slot window is exactly the decode kernel: slot 0 sees
        context_lens tokens — same math, same masking."""
        import jax.numpy as jnp
        from paddle_tpu.incubate.nn.pallas.paged_attention import (
            paged_attention, paged_attention_multi)

        q, kp, vp, bt = self._rand(t=1)
        cl = jnp.asarray(np.array([9, 3, 17, 8], np.int32))
        multi = paged_attention_multi(q, kp, vp, bt, cl,
                                      sm_scale=0.3, interpret=True)
        single = paged_attention(q[:, 0], kp, vp, bt, cl,
                                 sm_scale=0.3, interpret=True)
        np.testing.assert_allclose(np.asarray(multi[:, 0]),
                                   np.asarray(single),
                                   rtol=2e-6, atol=2e-6)

    def test_positions_past_window_never_read(self):
        """Per-slot causal masking: slot t sees context_lens + t
        tokens, so nothing past position context_lens + T - 2 is
        live — poisoning the rest of the pool can't change either
        the kernel's or the reference's output."""
        import jax.numpy as jnp
        from paddle_tpu.incubate.nn.pallas.paged_attention import (
            paged_attention_multi, paged_attention_multi_reference)

        T = 4
        q, kp, vp, bt = self._rand(t=T)
        cl_np = np.array([9, 3, 17, 8], np.int32)
        cl = jnp.asarray(cl_np)
        out = paged_attention_multi(q, kp, vp, bt, cl, sm_scale=0.3,
                                    interpret=True)
        ref = paged_attention_multi_reference(q, kp, vp, bt, cl,
                                              sm_scale=0.3)
        live = np.zeros((kp.shape[0], kp.shape[1]), bool)
        bt_np = np.asarray(bt)
        for b in range(len(cl_np)):
            for p in range(cl_np[b] + T - 1):  # widest slot's view
                live[bt_np[b, p // 8], p % 8] = True
        mask = jnp.asarray(live)[:, :, None, None]
        pk = jnp.where(mask, kp, 1e9)
        pv = jnp.where(mask, vp, -1e9)
        out2 = paged_attention_multi(q, pk, pv, bt, cl,
                                     sm_scale=0.3, interpret=True)
        ref2 = paged_attention_multi_reference(q, pk, pv, bt, cl,
                                               sm_scale=0.3)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(out2))
        np.testing.assert_array_equal(np.asarray(ref),
                                      np.asarray(ref2))


# ---------------------------------------------------------------------------
# ISSUE 19: speculative decoding + prefix caching e2e
# ---------------------------------------------------------------------------

def _counter_deltas(prefixes, fn):
    """Run fn() and return (result, {counter: delta}) for stats under
    the given name prefixes. Uses registry.snapshot() — which never
    CREATES stats — so zero-delta assertions can't self-satisfy."""
    before = {k: v for k, v in cmon.registry.snapshot().items()
              if k.startswith(prefixes)}
    out = fn()
    after = {k: v for k, v in cmon.registry.snapshot().items()
             if k.startswith(prefixes)}
    deltas = {k: after[k] - before.get(k, 0) for k in after
              if after[k] != before.get(k, 0)}
    return out, deltas


class _SpecRig:
    """Shared model + engines for the ISSUE-19 e2e suite. Every
    LLMEngine construction pays XLA compiles for its whole program
    set on CPU, so each configuration is built ONCE and reused —
    engines drain completely between tests, and token identity is
    batch-composition-independent by contract, so reuse is safe."""

    def __init__(self):
        self.model = tiny_model()
        rng = np.random.RandomState(1)
        # lens capped at 16 so the spec arms compile one fewer
        # prefill bucket — raggedness, not bucket count, is what the
        # identity gate exercises
        self.prompts = [list(rng.randint(1, 128, n))
                        for n in (1, 3, 8, 9, 13, 16, 14, 5)]
        prng = np.random.RandomState(6)
        self.prefix = list(prng.randint(1, 128, 16))  # 2 full blocks
        self.pfx_prompts = [self.prefix
                            + list(prng.randint(1, 128, n))
                            for n in (5, 9)]
        self.sp = SamplingParams(max_new_tokens=8)
        self._engines = {}
        self._want = {}

    def engine(self, key, **kw):
        if key not in self._engines:
            self._engines[key] = LLMEngine(
                self.model, max_batch=4, block_size=8,
                num_blocks=kw.pop("num_blocks", 64), **kw)
        return self._engines[key]

    def want(self, which="mixed"):
        """k=1/no-cache reference outputs from the shared baseline
        engine, computed once per prompt set."""
        if which not in self._want:
            prompts = (self.prompts if which == "mixed"
                       else self.pfx_prompts)
            self._want[which] = self.engine("base").generate(
                prompts, sampling=self.sp)
            assert self.engine("base").check_drained() == {}
        return self._want[which]


@pytest.fixture(scope="module")
def rig():
    return _SpecRig()


class TestSpeculativeDecodeE2E:
    def test_greedy_token_identity_all_k(self, rig):
        """ISSUE-19 gate: greedy spec decoding at k in {2, 4, 8} is
        token-identical to the k=1 baseline across 8 concurrent
        mixed-length requests."""
        want = rig.want()
        # ground the baseline itself against the sequential reference
        assert want[3] == ref_greedy(rig.model, rig.prompts[3], 8)
        hist0 = cmon.hist_get("serve/hist/accept_len").count
        for k in (2, 4, 8):
            eng = rig.engine(f"k{k}", spec_k=k)
            got, deltas = _counter_deltas(
                ("serve/spec/",),
                lambda: eng.generate(rig.prompts, sampling=rig.sp))
            assert got == want, f"spec_k={k} diverged from k=1"
            assert eng.check_drained() == {}
            assert eng.cache.allocator.used_blocks == 0
            assert deltas.get("serve/spec/proposed", 0) > 0
            assert 0 < deltas.get("serve/spec/accepted", 0) \
                <= deltas["serve/spec/proposed"]
            assert eng.state_summary()["spec_k"] == k
        assert cmon.hist_get("serve/hist/accept_len").count > hist0

    def test_temperature_identity(self, rig):
        """Verification re-samples every slot with the baseline's
        position-keyed seeds, so spec == k=1 holds at ANY
        temperature, not just greedy."""
        def run(eng):
            rids = [eng.add_request(
                p, SamplingParams(max_new_tokens=6, temperature=0.9,
                                  top_k=20, seed=7 + i))
                for i, p in enumerate(rig.prompts)]
            while eng.has_unfinished():
                eng.step()
            outs = [list(eng.get_request(r).output_ids)
                    for r in rids]
            assert eng.check_drained() == {}
            return outs

        assert run(rig.engine("k4", spec_k=4)) \
            == run(rig.engine("base"))

    def test_chaos_corrupt_storm_degrades_not_diverges(self, rig):
        """serve_spec_verify:corrupt replaces EVERY draft proposal:
        acceptance collapses to the guaranteed 1 token/round floor
        but the emitted tokens stay identical to baseline."""
        eng = rig.engine("k4", spec_k=4)
        with chaos.inject("serve_spec_verify", "corrupt") as rule:
            got, deltas = _counter_deltas(
                ("serve/spec/",),
                lambda: eng.generate(rig.prompts, sampling=rig.sp))
        assert got == rig.want()
        assert rule.triggers > 0
        # corrupted drafts only survive verification by COINCIDING
        # with the target's own choice — acceptance collapses from
        # ~100% to (near) zero while throughput floors at 1/round
        assert deltas["serve/spec/proposed"] > 0
        assert deltas.get("serve/spec/accepted", 0) \
            <= deltas["serve/spec/proposed"] * 0.2
        assert eng.check_drained() == {}

    def test_disarmed_paths_leave_zero_spec_prefix_counters(self, rig):
        """spec_k=1 + prefix_cache off is the pre-PR engine: no draft
        pools, no serve/spec/* or serve/prefix/* counter motion."""
        eng = rig.engine("base")
        assert eng.cache.k_draft is None
        assert eng.cache.v_draft is None
        _, deltas = _counter_deltas(
            ("serve/spec/", "serve/prefix/"),
            lambda: eng.generate(rig.prompts[:4], sampling=rig.sp))
        assert deltas == {}
        s = eng.state_summary()
        assert s["spec_k"] == 1 and s["prefix_cache"] is False


class TestPrefixCacheE2E:
    def test_shared_prefix_prefills_tail_only(self, rig):
        """Two requests sharing a 2-full-block prefix: the second
        maps the published blocks copy-on-write and prefills ONLY its
        uncached tail — tokens identical to the cache-off engine."""
        prompts = rig.pfx_prompts
        eng = rig.engine("prefix", prefix_cache=True)
        got, deltas = _counter_deltas(
            ("serve/prefix/",),
            lambda: eng.generate(prompts, sampling=rig.sp))
        assert got == rig.want("pfx")
        assert deltas["serve/prefix/hits"] == 1
        assert deltas["serve/prefix/blocks_shared"] == 2
        assert deltas["serve/prefix/prefill_tokens_saved"] == 16
        assert eng.check_drained() == {}
        assert eng.cache.allocator.used_blocks == 0

    def test_eviction_replay_spec_prefix_zero_leaks(self, rig):
        """The everything-on stress: spec_k=4 + prefix caching on a
        pool too small for the working set. Evicting a request whose
        table maps shared blocks must release only its references,
        mid-spec-round preemption must replay token-exactly, and the
        drained pool is empty — outputs identical to the plain k=1
        cache-off engine."""
        rng = np.random.RandomState(7)
        prefix = list(rng.randint(1, 128, 16))
        prompts = [prefix + list(rng.randint(1, 128, n))
                   for n in (3, 7, 11, 5, 9, 2)]
        want = rig.engine("base").generate(prompts, sampling=rig.sp)
        evict0 = cmon.stat_get("serve/evictions")
        tight = rig.engine("tight", num_blocks=11, spec_k=4,
                           prefix_cache=True)
        got = tight.generate(prompts, sampling=rig.sp)
        assert got == want
        assert cmon.stat_get("serve/evictions") > evict0
        assert tight.check_drained() == {}
        assert tight.cache.allocator.used_blocks == 0
        s = tight.state_summary()
        assert s["spec_k"] == 4 and s["prefix_cache"] is True


# ---------------------------------------------------------------------------
# ISSUE 19: PTA074 — refcount/COW sanitizer (runtime + static)
# ---------------------------------------------------------------------------

class TestPTA074:
    def test_runtime_cow_finding(self):
        msan.configure("serving")
        try:
            msan.clear_findings()
            a = BlockAllocator(8)
            (b0,) = a.alloc("a", 1)
            a.share("b", b0)
            before = cmon.stat_get("analysis/PTA074/findings")
            with pytest.raises(ValueError):
                a.check_cow(b0)
            assert cmon.stat_get(
                "analysis/PTA074/findings") == before + 1
            assert "PTA074" in [f.code for f in msan.findings()]
        finally:
            msan.disarm()
            msan.clear_findings()

    def test_runtime_lost_refcount_reclaim_finding(self):
        """The defensive half: a block physically reclaimed while
        some OTHER owner's table still maps it means a refcount was
        lost — the allocator reports it at the faulting deref."""
        msan.configure("serving")
        try:
            msan.clear_findings()
            a = BlockAllocator(8)
            (b0,) = a.alloc("a", 1)
            a.share("b", b0)
            a._refcnt[b0] = 1  # simulate the lost refcount
            before = cmon.stat_get("analysis/PTA074/findings")
            a.release("a")  # reclaims while "b" still maps b0
            assert cmon.stat_get(
                "analysis/PTA074/findings") == before + 1
        finally:
            msan.disarm()
            msan.clear_findings()

    def test_disarmed_cow_still_raises_but_silent(self):
        assert not msan.armed("serving")
        a = BlockAllocator(8)
        (b0,) = a.alloc("a", 1)
        a.share("b", b0)
        before = cmon.stat_get("analysis/PTA074/findings")
        with pytest.raises(ValueError):
            a.check_cow(b0)
        assert cmon.stat_get(
            "analysis/PTA074/findings") == before

    def test_static_lint_private_reach(self):
        from paddle_tpu.analysis.serving import lint_kv_source

        bad = ("def steal(alloc, b):\n"
               "    alloc._free.append(b)\n"
               "    del alloc._refcnt[b]\n")
        rep = lint_kv_source(bad, filename="x.py")
        assert [f.code for f in rep.findings] == ["PTA074",
                                                  "PTA074"]
        # `self._free` is some other class's own field — clean
        good = ("class Pool:\n"
                "    def free(self, b):\n"
                "        self._free.append(b)\n")
        assert lint_kv_source(good, filename="x.py").findings == []
        # the allocator module itself is exempt
        assert lint_kv_source(
            bad, filename="kv_cache.py").findings == []


# ---------------------------------------------------------------------------
# ISSUE 26: the pools ride the layer scan's carry and are updated in place
# ---------------------------------------------------------------------------

class _PagedRig:
    """Arguments for model_runner's three decode-shaped programs at a
    toy width, over pools of any depth (`draft_params` cuts the
    weights to match, as the engine does for its draft)."""

    B, T, BS, MAXB = 4, 3, 4, 8

    def __init__(self, pool_layers, num_blocks):
        import jax.numpy as jnp

        from paddle_tpu.inference.serving import model_runner as mr

        model = tiny_model(layers=3)
        params, cfg = mr.extract_params(model)
        self.params = mr.draft_params(params, pool_layers)
        self.kw = dict(n_head=cfg.num_heads, eps=cfg.layer_norm_eps,
                       block_size=self.BS)
        rng = np.random.RandomState(3)
        shape = (pool_layers, num_blocks, self.BS, cfg.hidden_size)
        # garbage, not zeros: a stale slot read or a write that lands
        # one layer off changes the result
        self.k = jnp.asarray(rng.randn(*shape), jnp.float32)
        self.v = jnp.asarray(rng.randn(*shape), jnp.float32)
        self.tables = jnp.asarray(
            rng.permutation(np.arange(1, num_blocks))
            [:self.B * self.MAXB].reshape(self.B, self.MAXB), jnp.int32)
        self.pos = jnp.asarray([3, 9, 14, 7], jnp.int32)
        self.ids = jnp.asarray(rng.randint(1, 128, (self.B, self.T)),
                               jnp.int32)
        self.temp = jnp.zeros(self.B, jnp.float32)
        self.top_k = jnp.zeros(self.B, jnp.int32)
        self.seeds = jnp.zeros((self.B, self.T), jnp.int32)

    def call(self, program, k, v, step=0, ids=None):
        """(function, positional arguments, pool argnums) of the
        `step`-th dispatch of one program over pools k, v."""
        import jax.numpy as jnp

        from paddle_tpu.inference.serving import model_runner as mr

        ids = self.ids if ids is None else ids
        pos = self.pos + step * (self.T if program == "verify" else 1)
        if program == "decode":
            return mr.decode_step, (
                self.params, ids[:, 0], pos, k, v, self.tables, pos + 1,
                self.temp, self.top_k, self.seeds[:, 0]), (3, 4)
        if program == "verify":
            return mr.verify_step, (
                self.params, ids, pos, k, v, self.tables, pos + 1,
                self.temp, self.top_k, self.seeds), (3, 4)
        # tail: one request whose first 8 + 4 * step tokens are cached
        start = 8 + self.BS * step
        return mr.prefill_tail_step, (
            self.params, jnp.tile(ids[:1], (1, 4))[:, :8],
            jnp.int32(start), jnp.int32(start + 6), k, v,
            self.tables[0], self.temp[0], self.top_k[0],
            self.seeds[0, 0]), (4, 5)


def _plain_layers(params, x, k_pool, v_pool, blk, off, attend, *,
                  n_head, eps):
    """What `model_runner._scan_layers_paged` must equal bit for bit,
    behind the same signature: a Python loop over layers that takes
    each layer's pool out as [N, BS, H, D], writes the new rows,
    attends over that one layer (its first block is block 0) and
    puts the layer back."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.text.models.gpt import (_layer_norm,
                                            _residual_layer_norm)

    n_layers, n, bs, hd = k_pool.shape
    heads = (n_head, hd // n_head)
    rows = x.shape[:-1] + heads
    for layer in range(n_layers):
        bp = jax.tree_util.tree_map(lambda a: a[layer],
                                    params["blocks"])
        kc = k_pool[layer].reshape(n, bs, *heads)
        vc = v_pool[layer].reshape(n, bs, *heads)
        h = _layer_norm(x, bp["ln1_w"], bp["ln1_b"], eps)
        q, k, v = jnp.split(h @ bp["qkv_w"] + bp["qkv_b"], 3,
                            axis=-1)
        kc = kc.at[blk, off].set(k.reshape(rows))
        vc = vc.at[blk, off].set(v.reshape(rows))
        attn = attend(q.reshape(rows), kc, vc, 0).reshape(x.shape)
        attn = attn @ bp["proj_w"] + bp["proj_b"]
        h2, x2 = _residual_layer_norm(attn, x, bp["ln2_w"],
                                      bp["ln2_b"], eps)
        ffn = jax.nn.gelu(h2 @ bp["fc1_w"] + bp["fc1_b"])
        x = x2 + (ffn @ bp["fc2_w"] + bp["fc2_b"])
        k_pool = k_pool.at[layer].set(kc.reshape(n, bs, hd))
        v_pool = v_pool.at[layer].set(vc.reshape(n, bs, hd))
    return x, k_pool, v_pool


class TestPoolsInPlace:
    @pytest.mark.parametrize("pool_layers", [3, 1])
    @pytest.mark.parametrize("program", ["decode", "verify", "tail"])
    def test_compiled_program_holds_no_second_pool(self, program,
                                                   pool_layers):
        """What keeps the per-step pool copy from coming back: with
        the pools donated, the compiled program's outputs alias both
        of them and its temporaries stay under half of ONE pool (a
        scan that takes the pools as xs and stacks them as ys reads
        1.3x BOTH pools here; pools in the carry, 0.1x one) — for
        the target's depth and for a draft's. What the TPU's own
        layouts add is in test_serving_tpu_compile.py."""
        import functools

        import jax

        rig = _PagedRig(pool_layers, num_blocks=2048)
        fn, args, pools = rig.call(program, rig.k, rig.v)
        mem = jax.jit(functools.partial(fn, **rig.kw),
                      donate_argnums=pools) \
            .lower(*args).compile().memory_analysis()
        one_pool = rig.k.size * rig.k.dtype.itemsize
        assert mem.alias_size_in_bytes >= 2 * one_pool
        assert mem.temp_size_in_bytes < one_pool // 2, (
            mem.temp_size_in_bytes / one_pool)

    @pytest.mark.parametrize("program,kernel", [
        ("decode", False), ("decode", True), ("verify", False),
        ("verify", True), ("tail", False)])
    def test_scan_equals_plain_loop_over_layers(self, program, kernel,
                                                monkeypatch):
        """Tokens of three dispatches, each over the pools the one
        before left and fed its first token, AND both pools at the
        end are bit-identical to the same program over the plain
        per-layer loop — dense reference and interpret-mode kernel
        alike (the tail has no kernel path)."""
        import functools

        import jax

        from paddle_tpu.inference.serving import model_runner as mr

        rig = _PagedRig(pool_layers=3, num_blocks=40)
        step_fn = rig.call(program, rig.k, rig.v)[0]
        path = dict(use_kernel=kernel, interpret=kernel) \
            if program != "tail" else {}

        def three_dispatches():
            # a jit of its own: it traces whichever layer loop
            # model_runner holds at its first call
            fn = jax.jit(functools.partial(step_fn, **rig.kw, **path))
            tokens, ids, k, v = [], rig.ids, rig.k, rig.v
            for step in range(3):
                tok, k, v = fn(*rig.call(program, k, v, step, ids)[1])
                tokens.append(np.asarray(tok).ravel())
                ids = (rig.ids + tok.ravel()[0]) % 128
            return np.concatenate(tokens), np.asarray(k), np.asarray(v)

        got = three_dispatches()
        monkeypatch.setattr(mr, "_scan_layers_paged", _plain_layers)
        want = three_dispatches()
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        # the dispatches did write, and not the same token each time
        assert not np.array_equal(got[1], np.asarray(rig.k))
        assert len(set(got[0].tolist())) > 1


# ---------------------------------------------------------------------------
# ISSUE 28: the sampler ranks the vocabulary only for a batch that
# asks for it
# ---------------------------------------------------------------------------

def _double_argsort_sample_tokens(logits, temperature, top_k, seeds):
    """`sample_tokens` as it stood before ISSUE 28, verbatim: the
    reference the batch-level switch must reproduce token for token."""
    import jax
    import jax.numpy as jnp

    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(lg, t, k, seed):
        ranks = jnp.argsort(jnp.argsort(-lg))
        keep = ranks < jnp.where(k > 0, k, vocab)
        lg = jnp.where(keep, lg, -jnp.inf)
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
        return jax.random.categorical(
            key, lg / jnp.maximum(t, 1e-6)).astype(jnp.int32)

    sampled = jax.vmap(draw)(logits, temperature, top_k, seeds)
    return jnp.where(temperature > 0, sampled, greedy)


_SV, _SB = 61, 24      # vocabulary and batch of the sampler cases


def _sampler_case(name):
    """(logits, temperature, top_k, seeds, the switch's case) of one
    named batch."""
    rng = np.random.RandomState(len(name))
    logits = rng.randn(_SB, _SV).astype(np.float32)
    temp = np.full((_SB,), 0.8, np.float32)
    topk = np.zeros((_SB,), np.int32)
    seeds = rng.randint(0, 2 ** 31, _SB).astype(np.uint32)
    case = 2
    if name == "all_greedy":
        temp[:], case = 0.0, 0
        topk[::2] = 3                  # a greedy row's k asks nothing
    elif name == "all_sampling_top_k_0":
        case = 1
    elif name.startswith("top_k_"):
        topk[:] = {"1": 1, "4": 4, "vocab": _SV,
                   "vocab_plus_3": _SV + 3}[name[len("top_k_"):]]
    elif name == "mixed_rows":
        temp[0::3], topk[2::3] = 0.0, 5
    elif name == "greedy_rows_filter_sampling_rows_do_not":
        temp[0::2], topk[0::2], case = 0.0, 5, 1
    elif name == "ties_across_kth":
        # every row the same few values, +0.0 and -0.0 among them, so
        # that the k-th place always falls inside a run of equals;
        # a high temperature spreads the draws over the whole kept set
        logits = rng.choice(
            np.array([-1.0, -0.0, 0.0, 0.5, 2.0], np.float32),
            size=(_SB, _SV))
        temp[:] = 6.0
        topk[:] = 2 + np.arange(_SB) % 11
    elif name == "verify_repeats":
        # what verify_step hands over: per-request values repeated
        # for each of its t_q slots
        t_q = 4
        temp = np.repeat(np.array([0.0, 0.9, 1.3] * 2, np.float32), t_q)
        topk = np.repeat(np.array([0, 0, 7] * 2, np.int32), t_q)
    else:
        raise KeyError(name)
    return logits, temp, topk, seeds, case


_SAMPLER_CASES = [
    "all_greedy", "all_sampling_top_k_0", "top_k_1", "top_k_4",
    "top_k_vocab", "top_k_vocab_plus_3", "mixed_rows",
    "greedy_rows_filter_sampling_rows_do_not", "ties_across_kth",
    "verify_repeats"]


class TestSamplerFollowsTheBatch:
    @pytest.mark.parametrize("name", _SAMPLER_CASES)
    def test_tokens_identical_to_the_double_argsort(self, name):
        import jax

        from paddle_tpu.inference.serving import model_runner as mr

        logits, temp, topk, seeds, _ = _sampler_case(name)
        new = jax.jit(mr.sample_tokens)
        old = jax.jit(_double_argsort_sample_tokens)
        drew = set()
        for round_ in range(8):        # eight seeds a row
            s = seeds + np.uint32(round_ * 7919)
            got = np.asarray(new(logits, temp, topk, s))
            np.testing.assert_array_equal(
                got, np.asarray(old(logits, temp, topk, s)))
            drew.update(got[temp > 0].tolist())
        if name != "all_greedy" and name != "top_k_1":
            assert len(drew) > 3       # the draws did vary

    @pytest.mark.parametrize("name", _SAMPLER_CASES)
    def test_host_tells_the_case_the_program_takes(self, name):
        """`sample_case` over the requests' SamplingParams is the
        index the program computes from its two arrays."""
        from paddle_tpu.inference.serving import model_runner as mr

        _, temp, topk, _, case = _sampler_case(name)
        assert mr.sample_case(
            SamplingParams(temperature=float(t), top_k=int(k))
            for t, k in zip(temp, topk)) == case
        assert int(mr._batch_case(temp, topk)) == case

    def test_sort_only_inside_the_ranked_branch(self):
        """The structure that makes greedy decode cheap: at the top
        level no sort, no random bits and no logits-sized select, but
        ONE switch whose index is reduced over the batch (outside any
        vmap: under one a cond is a select that runs every branch);
        the sort in branch 2 alone, once, without an index payload."""
        import jax
        import jax.extend

        from paddle_tpu.inference.serving import model_runner as mr

        logits, temp, topk, seeds, _ = _sampler_case("mixed_rows")
        jaxpr = jax.make_jaxpr(mr.sample_tokens)(
            logits, temp, topk, seeds).jaxpr

        def eqns(jp, through_cond):
            """Every equation of `jp` and of the jaxprs nested in it
            (jit, vmap's closed calls, ...), a cond's branches only
            with `through_cond`."""
            for e in jp.eqns:
                yield e
                if e.primitive.name == "cond" and not through_cond:
                    continue
                for sub in jax.core.jaxprs_in_params(e.params):
                    yield from eqns(sub, through_cond)

        top = list(eqns(jaxpr, through_cond=False))
        names = [e.primitive.name for e in top]
        assert names.count("cond") == 1
        for heavy in ("sort", "random_bits", "threefry2x32", "cumsum"):
            assert heavy not in names, heavy
        assert not [e for e in top if e.primitive.name == "select_n"
                    and e.outvars[0].aval.shape == logits.shape]
        switch = top[names.index("cond")]
        assert len(switch.params["branches"]) == 3
        # the index: scalar, made of reductions over the batch alone
        made_by = {v: e for e in jaxpr.eqns for v in e.outvars}
        seen, todo, reduced = set(), [switch.invars[0]], 0
        while todo:
            v = todo.pop()
            e = made_by.get(v)
            if e is None or id(e) in seen:
                continue
            seen.add(id(e))
            if e.primitive.name.startswith("reduce_"):
                assert e.invars[0].aval.shape == (_SB,)
                reduced += 1
                continue
            assert v.aval.shape == ()
            todo.extend(x for x in e.invars
                        if isinstance(x, jax.extend.core.Var))
        assert reduced == 2
        sorts = [[e for e in eqns(b.jaxpr, through_cond=True)
                  if e.primitive.name == "sort"]
                 for b in switch.params["branches"]]
        assert [len(s) for s in sorts] == [0, 0, 1]
        assert len(sorts[2][0].invars) == 1      # values alone
        assert not switch.params["branches"][0].jaxpr.eqns


class TestSampleCaseCounters:
    def test_counters_follow_the_batches(self):
        """Greedy requests, then a sampling one among them, then one
        that filters: each target dispatch counts under the case its
        batch takes, and under no other."""
        eng = LLMEngine(tiny_model(), max_batch=4, block_size=8,
                        num_blocks=32)
        greedy = SamplingParams(max_new_tokens=5)

        def steps(sampling):
            def run():
                eng.add_request([5, 6], greedy)
                eng.add_request([7, 8, 9], sampling)
                while eng.has_unfinished():
                    eng.step()
            return _counter_deltas(("serve/sample/",), run)[1]

        # 5 tokens a request: the prefill's and 4 decode dispatches;
        # with a request of 3 tokens, 2 dispatches hold both
        assert steps(greedy) == {"serve/sample/steps_greedy": 4}
        assert steps(SamplingParams(
            max_new_tokens=3, temperature=1.0, seed=3)) == {
                "serve/sample/steps_greedy": 2,
                "serve/sample/steps_drawn": 2}
        assert steps(SamplingParams(
            max_new_tokens=3, temperature=1.0, top_k=4, seed=3)) == {
                "serve/sample/steps_greedy": 2,
                "serve/sample/steps_ranked": 2}
        assert eng.check_drained() == {}

    def test_verify_dispatches_count_too(self, rig):
        """Speculation: one count a round, the verify dispatch's."""
        rounds = cmon.hist_get("serve/hist/accept_len").count
        _, deltas = _counter_deltas(
            ("serve/sample/",),
            lambda: rig.engine("k4", spec_k=4).generate(
                [[5, 6, 7]], sampling=SamplingParams(
                    max_new_tokens=6, temperature=0.9, top_k=5,
                    seed=1)))
        # one request: one acceptance length observed a round
        rounds = cmon.hist_get("serve/hist/accept_len").count - rounds
        assert rounds > 0
        assert deltas == {"serve/sample/steps_ranked": rounds}
