"""Block-allocated paged KV cache (the vLLM/Ragged-Paged-Attention
memory model, PAPERS.md arxiv 2604.15464, on TPU-native pools).

Generation workloads can't preallocate per-request [max_seq] KV
tensors — at 8+ concurrent mixed-length requests that wastes most of
HBM on padding. Instead the cache is a FIXED device pool of
fixed-size blocks per layer:

    k/v pools:  [num_layers, num_blocks, block_size, n_head * head_dim]

What a token's row holds is the model's business: the cache is told
its pools by their row widths (`rows`, from the model's runner).
GPT-2 has two, keys and values of `n_head * head_dim` each; a
latent-attention model has ONE, `[normalised latent | rotated key]`
(576 values for GLM-4.7-Flash), and no V pool. Allocator, tables and
admission do not know the difference.

A SECOND kind of state (PR 34): what a sequence keeps that does not
grow with its length — the tail of a short convolution's window, a
recurrent state — has no blocks and no table. The runner declares it
beside `rows` as `slot_state`, `(layers, *shape a layer)` an array,
and the cache holds one array `[layers, max_batch, *shape]` each,
addressed by the sequence's SLOT in the decode batch (the batch row
IS the slot; a prefill is told which slot it fills). They ride
`pools` behind the paged pools (`n_paged` says where they start), so
whatever zeroes, donates, re-adopts or rebuilds the pools does the
same to them; only `defrag`, which renumbers BLOCKS, passes them by.
Nothing frees a slot's state: the next prefill into the slot
overwrites it whole. `serve/state/{bytes_per_seq,layers}` gauge it
beside `serve/kv/{row_values,bytes_per_token}` (0 for a model
without).

CACHE GROUPS (PR 38): a model whose attentions are of two kinds, some
over the whole context and some over a sliding window, declares its
layers in GROUPS of equal size, each of one kind (`groups`: a window
a group, None for the whole context; the runner reads them from the
model). The pools then hold one group's layers, `[g, N, BS, width]`,
and block `n` holds the rows of `block_size` tokens of `g` layers of
WHICHEVER group owns it: one allocator, one free list, blocks of one
size. A sequence has one table a group, `[groups, max_blocks]`,
indexed by the LOGICAL block `position // block_size`, NULL where a
block was never granted or was freed. A window group holds the
blocks of the `window + 2` newest positions and no others (the
window, the token in flight, growth's lookahead: at most `ceil(window
/ BS) + 2` blocks, whatever the context): `grow()` returns what fell
behind to the free list before it takes what the next token needs,
for any sequence and any group to reuse, and `admit()` grants a long
prompt no more than that (its prefill scatters the earlier
positions' rows into the NULL block, the rule of a padded tail). All
of it is host bookkeeping: a freed block is only ever written by a
LATER dispatch than the last one that read it. A cache of one group
(every other model's) is the cache it was. No prefix sharing and no
speculation over a window group: a shared prefix's freed blocks and
a rejected draft's rows are ROADMAP R6's.

(heads and head_dim share the minor dimension: a token's K or V row
is `n_head * head_dim` contiguous values, stored row-major and
unpadded on the device. With `head_dim` alone as the minor dimension
— 64 of a TPU's 128 lanes — the device either pads every row to
twice its size or puts the BLOCK axis on the lanes, and every
compiled program then transposes whole pools on its way in and out:
PERF.md, PR 26.)

and every request owns a host-side BLOCK TABLE — the ordered list of
pool block ids covering its tokens. Token `t` of a request lives at
`(table[t // block_size], t % block_size)`. Attention reads K/V
through the table (dense gather fallback, or the Pallas ragged
paged-attention kernel in `incubate.nn.pallas.paged_attention`), so
sequences of wildly different lengths share one pool with ZERO
padding waste beyond the last partial block.

Block 0 is the reserved NULL block: padded prompt positions and
inactive batch slots write their garbage K/V there, so the compiled
programs never need a "don't write" branch — reads never see it
because every read is masked by the request's context length.

The allocator is the admission-control truth: `can_admit()` answers
whether a prompt fits, `alloc()`/`release()` move blocks between the
free list and per-owner tables, and the `serve/kv_blocks/{used,free}`
gauges (PR-1 monitor hub) track occupancy. Pool sizing comes from
`PADDLE_SERVE_POOL_BYTES` or — on devices with PJRT stats — from the
PR-5 `monitor.memory.memory_stats()` free-HBM reading, discounted by
the per-program footprints already resident.

Prefix caching (copy-on-write sharing): every block carries a
REFCOUNT, and FULL immutable blocks are published in a content-hash
index keyed by a chain hash (each block's digest folds in its
predecessor's, so a hit at depth i proves the whole prefix matches
AND that repeated identical chunks inside one prompt never collide).
`PagedKVCache.admit()` maps a new request's cached prefix blocks
into its table by bumping refcounts — no data movement — and
allocates only the uncached remainder; prefill then runs only the
tail. Shared blocks are immutable: the engine's write positions are
always >= the cached prefix, and `check_cow()` enforces it. A block
returns to the free list only when its LAST reference drops, which
also deregisters its hash (so eviction of one sharer never reclaims
— or republishes stale — shared content).

PTA07x (block-leak) accounting: with `PADDLE_SANITIZE=serving` armed,
double-free / free-of-unowned trips a PTA071 finding at the faulting
call, `audit_leaks(live_owners)` reports PTA070 for blocks still
owned by requests the serving layer no longer tracks, and PTA074
flags copy-on-write violations (a shared block written through, or a
block physically reclaimed while another table still maps it). The
static half lives in `paddle_tpu.analysis.serving`.
"""
from __future__ import annotations

import hashlib
import math
import os
from collections import deque

import numpy as np

from ...core import monitor as _cmon
from ...monitor import sanitize as _san

__all__ = ["BlockAllocator", "PagedKVCache", "NULL_BLOCK",
           "env_block_size", "env_pool_bytes", "env_max_batch",
           "env_spec_k", "env_spec_draft", "env_prefix_cache",
           "auto_num_blocks", "bytes_per_block", "prefix_hashes"]

NULL_BLOCK = 0  # reserved garbage-dump block, never owned

_DEF_BLOCK_SIZE = 16
_DEF_MAX_BATCH = 8
# pool budget on the CPU platform (its client has no memory stats) —
# big enough for the tests' tiny models, small enough to exercise
# eviction in the chaos flood
_CPU_POOL_BYTES = 64 << 20


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def env_block_size():
    """PADDLE_SERVE_BLOCK_SIZE — tokens per KV block (default 16)."""
    return max(1, _env_int("PADDLE_SERVE_BLOCK_SIZE", _DEF_BLOCK_SIZE))


def env_pool_bytes():
    """PADDLE_SERVE_POOL_BYTES — total KV pool budget in bytes
    (default 0 = auto-size from device memory stats)."""
    return _env_int("PADDLE_SERVE_POOL_BYTES", 0)


def env_max_batch():
    """PADDLE_SERVE_MAX_BATCH — decode batch width (default 8)."""
    return max(1, _env_int("PADDLE_SERVE_MAX_BATCH", _DEF_MAX_BATCH))


def env_spec_k():
    """PADDLE_SERVE_SPEC_K — speculative tokens per dispatch
    (default 1 = speculation off, plain one-token decode)."""
    return max(1, min(8, _env_int("PADDLE_SERVE_SPEC_K", 1)))


def env_spec_draft():
    """PADDLE_SERVE_SPEC_DRAFT — draft model layer count (default
    0 = auto: half the target's layers, minimum 1)."""
    return max(0, _env_int("PADDLE_SERVE_SPEC_DRAFT", 0))


def env_prefix_cache():
    """PADDLE_SERVE_PREFIX_CACHE — 1 enables copy-on-write prefix
    block sharing (default 0 = off)."""
    return 1 if _env_int("PADDLE_SERVE_PREFIX_CACHE", 0) else 0


def prefix_hashes(tokens, block_size, n_blocks=None):
    """Chain hashes for the leading FULL blocks of a token sequence:
    digest(i) = sha256(digest(i-1) || tokens of block i). The chain
    makes a depth-i hit prove the entire prefix matches and keeps
    repeated identical chunks within one prompt distinct."""
    if n_blocks is None:
        n_blocks = len(tokens) // block_size
    out = []
    h = b"\x00" * 32
    for i in range(n_blocks):
        m = hashlib.sha256()
        m.update(h)
        m.update(np.asarray(tokens[i * block_size:(i + 1) * block_size],
                            np.int64).tobytes())
        h = m.digest()
        out.append(h)
    return out


def bytes_per_block(num_layers, block_size, n_head=None, head_dim=None,
                    dtype=np.float32, rows=None):
    """HBM cost of ONE block id across all layers and pools: `rows`
    are the pools' row widths, by default a K and a V pool of
    `n_head * head_dim` values each."""
    if rows is None:
        rows = (n_head * head_dim,) * 2
    itemsize = np.dtype(dtype).itemsize
    return num_layers * block_size * sum(rows) * itemsize


def auto_num_blocks(per_block, pool_bytes=None, fraction=0.45):
    """Pool size in blocks: the explicit budget when given (env or
    argument), else `fraction` of the device's free HBM per the PR-5
    memory stats (bytes_limit - bytes_in_use already accounts for the
    resident compiled programs + params). Only the CPU platform, whose
    client reports no stats, gets the fixed 64 MiB budget; an
    accelerator that does not report `bytes_limit` is an error."""
    budget = pool_bytes if pool_bytes else env_pool_bytes()
    if not budget:
        import jax

        from ...monitor import memory as _memory

        dev = jax.devices()[0]
        if dev.platform == "cpu":
            budget = _CPU_POOL_BYTES
        else:
            stats = _memory.memory_stats(dev)
            limit = int(stats.get("bytes_limit") or 0)
            used = int(stats.get("bytes_in_use") or 0)
            if limit <= used:
                raise RuntimeError(
                    f"cannot size the KV pool on {dev}: PJRT memory "
                    f"stats report bytes_limit={limit}, bytes_in_use="
                    f"{used} (source={stats.get('source')!r}); set "
                    "PADDLE_SERVE_POOL_BYTES to size it explicitly")
            budget = int((limit - used) * fraction)
    # +1: block 0 is the null block, not usable capacity
    return max(2, budget // max(1, per_block) + 1)


class BlockAllocator:
    """Host-side free-list over the pool's block ids.

    Block 0 (NULL_BLOCK) is never handed out. Ownership is tracked
    per request id so leaks are attributable: `release(owner)` drops
    every reference an owner holds, `audit_leaks(live)` reports
    blocks owned by ids the caller no longer tracks (PTA070).

    Refcounts: a freshly allocated block has refcount 1; `share()`
    maps it into another owner's table copy-on-write (refcount up,
    no data movement). A block is physically reclaimed — returned to
    the free list and dropped from the content-hash index — only
    when its LAST reference goes, so evicting one sharer can never
    free (or stale-publish) blocks another request still reads."""

    def __init__(self, num_blocks):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (1 null + 1 usable), got "
                f"{num_blocks}")
        self.num_blocks = int(num_blocks)
        self._free = deque(range(1, self.num_blocks))
        self._owned = {}  # owner id -> [block ids]
        self._refcnt = {}  # block id -> live references
        self._by_hash = {}  # chain digest -> block id
        self._hash_of = {}  # block id -> chain digest
        self._sync_gauges()

    # -- occupancy ---------------------------------------------------
    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def used_blocks(self):
        return self.num_blocks - 1 - len(self._free)

    def owners(self):
        return sorted(self._owned)

    def owned(self, owner):
        return list(self._owned.get(owner, ()))

    def can_alloc(self, n):
        return len(self._free) >= n

    def refcount(self, block_id):
        return self._refcnt.get(block_id, 0)

    def _sync_gauges(self):
        _cmon.stat_set("serve/kv_blocks/used", self.used_blocks)
        _cmon.stat_set("serve/kv_blocks/free", self.free_blocks)

    # -- alloc/free --------------------------------------------------
    def alloc(self, owner, n=1):
        """Give `owner` `n` more blocks; returns the new block ids, or
        None when the pool can't satisfy the request (the caller's cue
        to evict — never a partial grant)."""
        if n <= 0:
            return []
        if len(self._free) < n:
            return None
        got = [self._free.popleft() for _ in range(n)]
        for b in got:
            self._refcnt[b] = 1
        self._owned.setdefault(owner, []).extend(got)
        self._sync_gauges()
        return got

    def share(self, owner, block_id):
        """Map a LIVE block into `owner`'s table copy-on-write: the
        refcount goes up and nothing moves. The callers' contract is
        that shared blocks are full and immutable — `check_cow`
        enforces it on write paths."""
        if block_id == NULL_BLOCK or block_id not in self._refcnt:
            raise ValueError(
                f"cannot share unallocated block {block_id}")
        self._refcnt[block_id] += 1
        self._owned.setdefault(owner, []).append(block_id)
        self._sync_gauges()
        return block_id

    def _deref(self, block_id):
        """Drop one reference; physically reclaim on the last one.
        Returns 1 when the block actually hit the free list."""
        rc = self._refcnt.get(block_id, 1) - 1
        if rc > 0:
            self._refcnt[block_id] = rc
            return 0
        self._refcnt.pop(block_id, None)
        digest = self._hash_of.pop(block_id, None)
        if digest is not None and self._by_hash.get(digest) == block_id:
            del self._by_hash[digest]
        if getattr(_san, "_serving", False):
            # defensive PTA074 half: reclaiming a block some OTHER
            # table still maps means a refcount was lost somewhere
            holders = [o for o, bl in self._owned.items()
                       if block_id in bl]
            if holders:
                _san._emit(
                    "PTA074",
                    f"block {block_id} physically reclaimed while "
                    f"still mapped by {holders!r} (refcount lost)",
                    dedup=("PTA074", "reclaim", block_id))
        self._free.append(block_id)
        return 1

    def check_cow(self, block_id):
        """Copy-on-write guard: a block mapped by more than one
        request is immutable — writing through it would corrupt a
        stranger's context. PTA074 when the serving sanitizer is
        armed, ValueError always."""
        rc = self._refcnt.get(block_id, 1)
        if rc > 1:
            if getattr(_san, "_serving", False):
                _san._emit(
                    "PTA074",
                    f"write to shared block {block_id} (refcount "
                    f"{rc}) without copy-on-write",
                    dedup=("PTA074", "cow", block_id))
            raise ValueError(
                f"block {block_id} is shared by {rc} requests and "
                f"immutable (copy-on-write required)")
        return block_id

    # -- content-hash index (prefix cache) ---------------------------
    def register_hash(self, block_id, digest):
        """Publish one full immutable block under its chain digest.
        Lookup-first: an already-published digest (or an already-
        published block) keeps its existing mapping. Returns 1 on a
        new registration, 0 on skip."""
        if digest in self._by_hash or block_id in self._hash_of:
            return 0
        if block_id == NULL_BLOCK or block_id not in self._refcnt:
            raise ValueError(
                f"cannot index unallocated block {block_id}")
        self._by_hash[digest] = block_id
        self._hash_of[block_id] = digest
        return 1

    def lookup_hash(self, digest):
        return self._by_hash.get(digest)

    def clear_hash_index(self):
        """Forget every published block — pool resets zero the K/V
        contents, so pre-reset digests would serve garbage."""
        self._by_hash.clear()
        self._hash_of.clear()

    def release(self, owner):
        """Drop every reference `owner` holds; returns how many
        references were dropped (shared blocks stay resident for
        their other owners). Unknown owners are a no-op (a request
        evicted before its first alloc has nothing to free)."""
        blocks = self._owned.pop(owner, None)
        if not blocks:
            return 0
        for b in blocks:
            self._deref(b)
        self._sync_gauges()
        return len(blocks)

    def free_one(self, owner, block_id):
        """Return one specific block (shrink paths). Freeing a block
        the owner doesn't hold is the double-free bug class — PTA071
        when the serving sanitizer is armed, ValueError always."""
        blocks = self._owned.get(owner)
        if not blocks or block_id not in blocks:
            if getattr(_san, "_serving", False):
                _san._emit(
                    "PTA071",
                    f"free of block {block_id} not owned by "
                    f"{owner!r} (double-free or foreign free)",
                    dedup=("PTA071", owner, block_id))
            raise ValueError(
                f"block {block_id} is not owned by {owner!r}")
        blocks.remove(block_id)
        if not blocks:
            self._owned.pop(owner, None)
        self._deref(block_id)
        self._sync_gauges()
        return block_id

    # -- leak audit (PTA070 runtime half) ----------------------------
    def audit_leaks(self, live_owners=()):
        """Blocks owned by request ids the serving layer no longer
        tracks are leaked — every completed/evicted/aborted request
        must have released. Returns {owner: [blocks]} of leaks; with
        the `serving` sanitize family armed each leak also emits a
        PTA070 finding through the PR-9 machinery."""
        live = set(live_owners)
        leaked = {o: list(b) for o, b in self._owned.items()
                  if o not in live and b}
        if leaked and getattr(_san, "_serving", False):
            for owner, blocks in sorted(leaked.items(),
                                        key=lambda kv: str(kv[0])):
                _san._emit(
                    "PTA070",
                    f"KV block leak: {len(blocks)} block(s) still "
                    f"owned by finished/unknown request {owner!r}",
                    dedup=("PTA070", owner))
        return leaked


class _GroupTables:
    """One sequence's tables in a cache of several groups: `rows`
    [groups, max_blocks] int32 by logical block (NULL where none is
    held), `lo[g]` the first logical block group g holds, `hi` the
    logical blocks granted so far (the same in every group)."""

    __slots__ = ("rows", "lo", "hi")

    def __init__(self, rows, lo, hi):
        self.rows, self.lo, self.hi = rows, lo, hi


class PagedKVCache:
    """The device pools + the allocator + per-request block tables."""

    def __init__(self, num_layers, num_heads=None, head_dim=None,
                 block_size=None, num_blocks=None, pool_bytes=None,
                 dtype=None, draft_layers=0, prefix_cache=False,
                 rows=None, slot_state=(), max_batch=0, groups=(None,),
                 max_seq_len=0):
        import jax.numpy as jnp

        self.block_size = int(block_size or env_block_size())
        # `num_layers` is a GROUP's; one group: the model's
        self.num_layers = int(num_layers)
        # a window a cache group (None: the whole context)
        self.groups = tuple(None if w is None else int(w) for w in groups)
        if len(self.groups) > 1 and (prefix_cache or draft_layers):
            raise ValueError("no prefix sharing or draft pools over "
                             "several cache groups")
        # owner -> _GroupTables; None in a cache of one group, whose
        # tables are the allocator's lists
        self._seqs = {} if len(self.groups) > 1 else None
        self._max_blocks = math.ceil(int(max_seq_len) / self.block_size)
        # one pool per entry, each `[L, N, BS, width]`
        self.rows = tuple(int(w) for w in (
            rows or (num_heads * head_dim,) * 2))
        self.n_paged = len(self.rows)
        # per-slot arrays behind the paged pools, `[layers,
        # max_batch, *shape]` each
        self.slot_state = tuple(
            (int(s[0]), int(max_batch)) + tuple(int(d) for d in s[1:])
            for s in slot_state)
        self.dtype = jnp.dtype(dtype or jnp.float32)
        self.draft_layers = int(draft_layers)
        self.prefix_cache = bool(prefix_cache)
        per_block = bytes_per_block(num_layers, self.block_size,
                                    dtype=self.dtype, rows=self.rows)
        if num_blocks is None:
            num_blocks = auto_num_blocks(per_block,
                                         pool_bytes=pool_bytes)
        self.num_blocks = int(num_blocks)
        # draft-model twin pools address through the SAME allocator
        # and tables — the chain-hash identity that lets two requests
        # share target KV holds for draft KV too, so one refcount
        # covers both
        self.draft_pools = None
        # bumped whenever block ids are renumbered (defrag): a table
        # built before is stale after
        self.epoch = 0
        self._zero_pools()
        self.allocator = BlockAllocator(self.num_blocks)
        _cmon.stat_set("serve/kv/row_values", sum(self.rows))
        _cmon.stat_set("serve/kv/bytes_per_token",
                       per_block // self.block_size)
        _cmon.stat_set("serve/state/layers",
                       sum(s[0] for s in self.slot_state))
        _cmon.stat_set("serve/state/bytes_per_seq", sum(
            s[0] * math.prod(s[2:]) for s in self.slot_state)
            * self.dtype.itemsize)
        _cmon.stat_set("serve/kv/groups", len(self.groups))
        _cmon.stat_set("serve/kv/window",
                       max((w or 0 for w in self.groups)))

    def _zero_pools(self):
        import jax.numpy as jnp

        def zeros(layers):
            return tuple(
                jnp.zeros((layers, self.num_blocks, self.block_size, w),
                          self.dtype) for w in self.rows)

        self.pools = zeros(self.num_layers) + tuple(
            jnp.zeros(s, self.dtype) for s in self.slot_state)
        if self.draft_layers:
            self.draft_pools = zeros(self.draft_layers)

    def _pool_by_name(group, i):
        """The i-th pool of `pools` / `draft_pools` as an attribute:
        the two pools of a keys-and-values cache, by name."""
        def get(self):
            pools = getattr(self, group)
            return pools and pools[i]

        def put(self, value):
            pools = list(getattr(self, group))
            pools[i] = value
            setattr(self, group, tuple(pools))
        return property(get, put)

    k, v = _pool_by_name("pools", 0), _pool_by_name("pools", 1)
    k_draft = _pool_by_name("draft_pools", 0)
    v_draft = _pool_by_name("draft_pools", 1)
    del _pool_by_name

    # -- geometry ----------------------------------------------------
    def blocks_for_tokens(self, n_tokens):
        return max(1, math.ceil(n_tokens / self.block_size))

    def _first_block(self, n_tokens, window):
        """The first logical block a group of `window` holds of a
        sequence whose cache is to cover `n_tokens` tokens: the block
        of the oldest of the `window + 2` newest positions (the
        window of the deepest query still to be made, one position
        for a dispatch that is made again after a failure, one of
        lookahead)."""
        if window is None:
            return 0
        return max(0, n_tokens - window - 2) // self.block_size

    def blocks_needed(self, n_tokens, lookahead_blocks=0):
        """Blocks, over all groups, that a sequence of `n_tokens`
        tokens holds, and `lookahead_blocks` more a group."""
        top = self.blocks_for_tokens(n_tokens)
        return sum(top - self._first_block(n_tokens, w) + lookahead_blocks
                   for w in self.groups)

    def can_admit(self, n_tokens, lookahead_blocks=1,
                  cached_blocks=0):
        """Admission control: room for the prompt's blocks (less any
        already cached) plus a decode lookahead so a request admitted
        now can generate at least one block of tokens before pool
        pressure. Speculative decoding passes a k-aware lookahead —
        a verify dispatch can land up to k tokens at once."""
        need = max(0, self.blocks_needed(n_tokens) - cached_blocks) \
            + lookahead_blocks * len(self.groups)
        return self.allocator.can_alloc(need)

    # -- growth, freeing, release ------------------------------------
    def short(self, owner, n_tokens):
        """Blocks `grow(owner, n_tokens)` would take off the free
        list (what it returns to it first is left out)."""
        if self._seqs is None:
            return max(0, self.blocks_for_tokens(n_tokens)
                       - len(self.allocator.owned(owner)))
        return max(0, self.blocks_for_tokens(n_tokens)
                   - self._seqs[owner].hi) * len(self.groups)

    def grow(self, owner, n_tokens):
        """Make `owner`'s tables cover `n_tokens` tokens. A window
        group first returns the blocks whose every position has
        fallen behind (`_first_block`) to the free list. False when
        the pool cannot cover the rest (the caller's cue to evict;
        never a partial grant)."""
        short = self.short(owner, n_tokens)
        if self._seqs is None:
            return self.allocator.alloc(owner, short) is not None
        seq = self._seqs[owner]
        freed = 0
        for g, window in enumerate(self.groups):
            first = min(self._first_block(n_tokens, window), seq.hi)
            for b in range(seq.lo[g], first):
                self.allocator.free_one(owner, int(seq.rows[g, b]))
                seq.rows[g, b] = NULL_BLOCK
                freed += 1
            seq.lo[g] = max(seq.lo[g], first)
        if freed:
            _cmon.stat_add("serve/kv/window_blocks_freed", freed)
        got = self.allocator.alloc(owner, short)
        if got is None:
            return False
        if got:
            top = seq.hi + short // len(self.groups)
            seq.rows[:, seq.hi:top] = np.reshape(got, (len(self.groups), -1))
            seq.hi = top
        return True

    def held(self, owner):
        """(blocks `owner` holds in the groups over the whole
        context, in the window groups)."""
        if self._seqs is None:
            return len(self.allocator.owned(owner)), 0
        seq = self._seqs[owner]
        counts = [seq.hi - lo for lo in seq.lo]
        full = sum(c for c, w in zip(counts, self.groups) if w is None)
        return full, sum(counts) - full

    def window_blocks_least(self, n_tokens):
        """The blocks, over the window groups, that hold a position
        a query over `n_tokens` tokens can see."""
        last = (n_tokens - 1) // self.block_size
        return sum(last - max(0, n_tokens - w) // self.block_size + 1
                   for w in self.groups if w is not None)

    def release(self, owner):
        """Drop every block `owner` holds, in every group; returns
        how many references were dropped."""
        if self._seqs is not None:
            self._seqs.pop(owner, None)
        return self.allocator.release(owner)

    # -- prefix cache ------------------------------------------------
    def probe_prefix(self, tokens):
        """(cached_blocks, block_ids): the longest chain of leading
        FULL blocks already published, capped BELOW the full context
        so the tail prefill always has >= 1 real token to run (and a
        row to sample from)."""
        if not self.prefix_cache or not len(tokens):
            return 0, []
        cap = max(0, (len(tokens) - 1) // self.block_size)
        ids = []
        for digest in prefix_hashes(tokens, self.block_size, cap):
            b = self.allocator.lookup_hash(digest)
            if b is None:
                break
            ids.append(b)
        return len(ids), ids

    def admit(self, owner, tokens):
        """Atomically give `owner` the blocks for its context: cached
        prefix blocks map copy-on-write (shared ids lead the table,
        matching their token positions), only the remainder comes off
        the free list. Returns the cached TOKEN count (0 when the
        cache is off or cold), or None when the pool can't cover the
        uncached remainder — never a partial grant."""
        if self._seqs is not None:
            return self._admit_groups(owner, len(tokens))
        total = self.blocks_for_tokens(len(tokens))
        n_shared, shared = self.probe_prefix(tokens)
        fresh = total - n_shared
        if not self.allocator.can_alloc(fresh):
            return None
        for b in shared:
            self.allocator.share(owner, b)
        if fresh and self.allocator.alloc(owner, fresh) is None:
            for b in shared:  # can't happen single-threaded; unwind
                self.allocator.free_one(owner, b)
            return None
        if n_shared:
            _cmon.stat_add("serve/prefix/hits", 1)
            _cmon.stat_add("serve/prefix/blocks_shared", n_shared)
        return n_shared * self.block_size

    def _admit_groups(self, owner, n_tokens):
        """`admit()` in a cache of several groups: every group its
        own blocks from `_first_block` on, all or none."""
        top = self.blocks_for_tokens(n_tokens)
        lo = [self._first_block(n_tokens, w) for w in self.groups]
        got = self.allocator.alloc(owner, sum(top - f for f in lo))
        if got is None:
            return None
        rows = np.full((len(self.groups), self._max_blocks), NULL_BLOCK,
                       np.int32)
        at = 0
        for g, f in enumerate(lo):
            rows[g, f:top] = got[at:at + top - f]
            at += top - f
        self._seqs[owner] = _GroupTables(rows, lo, top)
        return 0

    def register_prefix(self, owner, tokens):
        """Publish `owner`'s full prompt blocks (written, immutable
        from here on) in the content index so later admissions can
        share them. Lookup-first — blocks already published, and
        digests already claimed, keep their existing mapping. Decode
        extends context into NEW blocks only, so published content
        never mutates. Returns how many blocks were newly published."""
        if not self.prefix_cache:
            return 0
        blocks = self.allocator.owned(owner)
        full = min(len(tokens) // self.block_size, len(blocks))
        n = 0
        for i, digest in enumerate(
                prefix_hashes(tokens, self.block_size, full)):
            n += self.allocator.register_hash(blocks[i], digest)
        return n

    def block_table(self, owner, max_blocks):
        """Padded int32 device-table row for one request: its owned
        blocks in token order, NULL_BLOCK beyond. In a cache of
        several groups a row a group, `[groups, max_blocks]`, by
        logical block (the sequence's own array: the caller copies
        it into its batch)."""
        if self._seqs is not None:
            return self._seqs[owner].rows
        blocks = self.allocator.owned(owner)
        if len(blocks) > max_blocks:
            raise ValueError(
                f"request {owner!r} holds {len(blocks)} blocks > "
                f"max_blocks_per_seq={max_blocks}")
        row = np.full((max_blocks,), NULL_BLOCK, np.int32)
        row[:len(blocks)] = blocks
        return row

    def reset_pools(self):
        """Fresh zero pools (and per-slot state) — recovery after a
        failed DONATING dispatch consumed the old ones (a real
        RESOURCE_EXHAUSTED mid-execution deletes donated buffers).
        The caller must re-prefill every sequence: allocator state
        survives but the K/V contents and every slot's state are
        gone."""
        self._zero_pools()
        # zeroed pools invalidate every published prefix — serving a
        # pre-reset digest would share garbage KV
        self.allocator.clear_hash_index()

    # -- defrag ------------------------------------------------------
    def defrag(self):
        """Compact allocated blocks to the front of the pool (one
        device gather per pool) so a long-lived server's free list
        stays contiguous — contiguous tables DMA better through the
        paged kernel's block streaming. Returns the number of blocks
        that moved; owner tables are rewritten in place."""
        owners = self.allocator.owners()
        mapping = {NULL_BLOCK: NULL_BLOCK}
        nxt = 1
        for owner in owners:
            for b in self.allocator._owned[owner]:
                if b not in mapping:  # shared blocks move ONCE
                    mapping[b] = nxt
                    nxt += 1
        moved = sum(1 for old, new in mapping.items() if old != new)
        if not moved:
            return 0
        self.epoch += 1
        # perm[new] = old; untouched tail keeps identity so freed
        # block contents (never read — reads are context-masked) need
        # no care beyond staying in range
        perm = np.arange(self.num_blocks)
        for old, new in mapping.items():
            perm[new] = old
        import jax.numpy as jnp

        idx = jnp.asarray(perm)
        # the per-slot state has no blocks to renumber
        self.pools = tuple(p[:, idx] for p in self.pools[:self.n_paged]) \
            + self.pools[self.n_paged:]
        if self.draft_pools is not None:
            self.draft_pools = tuple(p[:, idx]
                                     for p in self.draft_pools)
        for owner in owners:
            self.allocator._owned[owner] = [
                mapping[b] for b in self.allocator._owned[owner]]
        if self._seqs:
            renumber = np.arange(self.num_blocks, dtype=np.int32)
            for old, new in mapping.items():
                renumber[old] = new
            for seq in self._seqs.values():
                seq.rows = renumber[seq.rows]
        self.allocator._refcnt = {
            mapping[b]: c
            for b, c in self.allocator._refcnt.items()}
        self.allocator._by_hash = {
            h: mapping[b]
            for h, b in self.allocator._by_hash.items()}
        self.allocator._hash_of = {
            mapping[b]: h
            for b, h in self.allocator._hash_of.items()}
        self.allocator._free = deque(
            range(nxt, self.num_blocks))
        self.allocator._sync_gauges()
        return moved
