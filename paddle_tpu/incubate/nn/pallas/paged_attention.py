"""Pallas TPU ragged paged-attention decode kernels: one for pools
of per-head keys and values (GPT-2's runner), one for a pool of
latent rows (the MLA runner's; the last section of this text).

The serving-side siblings of `attention_pallas.py` (PR 8): that kernel
streams contiguous K/V tiles for TRAINING-shaped batches; these
read K/V through per-request BLOCK TABLES out of the paged pools
(`inference.serving.kv_cache`), so ONE launch covers every sequence
in a continuous-batching decode step at mixed context lengths — the
Ragged Paged Attention design (PAPERS.md arxiv 2604.15464).

Decode shape: one query token per sequence.

    q            [B, H, D]           this step's query rows
    k/v pool     [N, BS, H, D]       a paged pool: one layer's, or every
                                     layer's stacked on the block axis
                                     with the tables shifted to the
                                     layer (serving.model_runner)
    block_tables [B, MAXB] int32     pool block id per (seq, slot)
    context_lens [B]       int32     real tokens per sequence

Multi-query decode (`paged_attention_multi`): the speculative-decode
verify dispatch feeds T consecutive query tokens per sequence — q is
[B, T, H, D], query slot `t` of sequence `b` sits at absolute
position `context_lens[b] - 1 + t` and may attend over
`context_lens[b] + t` tokens (itself included). ONE kernel body
serves both: decode is the T = 1 case, and a window's slots are more
rows of the same tiles, each masked at its own depth (T <= 8).

Grid: (B,), one sequence a grid step; the body walks the sequence's
LIVE page groups itself (`fori_loop` to `ceil(context / R)`), G
pages a group, G from the row's bytes (`_pages_per_group`: 1024 rows
of 1 KB, 256 of GPT-2's f32 rows of 4 KB; the table is padded to a
multiple of G where it is not one). A call with a `window` counts its
groups from the page that holds the window's first key, G from the
window (`_window_pages`: ONE group a sequence, 65 pages of 16 for a
window of 1024). The pools stay where they are
(`pl.ANY`); `block_tables`/`context_lens` ride as SCALAR PREFETCH
arguments (pltpu.PrefetchScalarGridSpec) and the body copies page
`tables[b, j*G + g]` into row g*BS of one of two [R, H*D] tiles, in
a rolled loop over the group's live pages (no branch a page), and
waits for them in one wait a pool for each binary digit of their
number: the pages of group j + 1 (or, from a sequence's last group,
of the next sequence's first) are on their way while group j is
multiplied. Only pages that hold a visible token are copied —
exactly the blocks each sequence owns, in table order, nothing else
— and only groups that hold one are walked: a dead page costs
nothing, not even a grid step. `kernels/paged/rows_<R>` counts the
calls by their group's rows while a program is traced. Inside the
last live group the positions past the context
mask to -inf (and the rows no copy wrote are zeroed in V), so they
contribute exactly zero weight. Online softmax (running
max/denominator in VMEM scratch) accumulates across a sequence's
groups, so nothing [S, S]-shaped ever materializes. (PR 10's grid,
(B, MAXB) with a page a step through `BlockSpec`s, was 2048 steps a
layer at the offline cell's shape, 17 of them live for 270 tokens;
the same through G `BlockSpec`s a step spent a third of its time on
the pipeline's per-operand bookkeeping of dead steps: PERF.md, PR 30.)

Layout inside the kernel: everything is 2-D and lane-dense, heads on
the sublanes. A pool block is read as [BS, H*D] (the pool's own
memory order, no transpose; Mosaic refuses 3-D reshapes of small
tiles) and the G pages of a step lie row over row in one [R, H*D]
tile. Head h's query sits on its own D lanes of row h, zeros on the
others:

    scores   [T*H, R]   = Qx[T*H, H*D] . K[R, H*D]^T   row t*H + h:
                          slot t, head h; one product for all heads
    output   [T*H, H*D] += P[T*H, R] @ V[R, H*D]       every row meets
                          every head's lanes; the last group's step
                          keeps row h's own D lanes (a 0/1 mask) and
                          sums the H rows into [T, H*D]

so there is no per-head selector product and no rounding but the
reference's own. An f32 pool is multiplied at `Precision.HIGHEST`.

Grouped K/V heads (PR 34): a pool may hold FEWER heads than the
query has, `[N, BS, Hkv, D]` under `q [B, H, D]` with `H = G * Hkv`;
query head `h` reads K/V head `h // G`. A page is then `[BS, Hkv*D]`
(what a token leaves), and the G query heads of one K/V head are G
more ROWS over the same lanes: the wrapper hands the kernel the
query as `[T*G, Hkv*D]` (row `t*G + g` holds heads `g, G + g, ...`,
one a K/V head's D lanes), the kernel spreads each row over `Hkv`
rows exactly as it spreads a slot's, and every tile is read once
for all G. `G = 1` is the kernel it was, to the letter.

Latent rows (`paged_latent_attention`, PR 33). What an MLA model
caches of a token is ONE row an attention, `[c_kv | k_rope]` (576
values stored in 640), and in the absorbed form that row is the key
AND the value of every head: multi-query attention with one shared
head. The same walk (grid (B,), the pool in `pl.ANY`, tables and
lengths as scalar prefetch, live groups only, two tiles deep, the
next sequence's first group started from the last of this one:
`_first_copies`, `_next_copies`, `_softmax_step` are shared) over
other operands:

    q      [Hp, row]   the absorbed query `q_nope W^K | q_rope`, zeros
                       to the stored width, H padded to the dtype's
                       sublane tile (20 -> 32 in bf16)
    tile   [R, row]    a group's pages, copied ONCE: both operands
    s      [Hp, R]   = q . tile^T
    acc    [Hp, row] += p . tile     the first `kv_lora_rank` lanes of
                       acc / l are o_lat, which W^V expands outside

There is no V pool and no head selector. A row is 1280 B and a page
20 KB where GPT-2's is 64 KB a pool; the rows a group come from the
same rule (`_pages_per_group`: 512 rows of 640 bf16 values) and the
copies of a live group are issued whole, unrolled, with no branch a
page and ONE wait: the table's NULL and padded columns name real
blocks, and the rows past the context are masked in the scores and
zeroed as values.

`interpret=True` runs the same kernels through the Pallas interpreter
for CPU parity tests (the PR-8 contract; see
`paged_attention_reference` for the dense gather it must match, and
`text.models.mla.mla_attend_absorbed` for the latent kernel's).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ....core import monitor as _cmon

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_attention_multi", "paged_attention_multi_reference",
           "paged_latent_attention", "paged_decode_supported"]

_NEG_INF = -1e30


def paged_decode_supported(num_heads, head_dim, block_size,
                           num_kv_heads=None):
    """Do the decode and verify programs attend through the kernel
    here? Answered from what the code can observe, with no switch of
    its own: the platform (a TPU; on the CPU only the interpreter,
    PADDLE_PALLAS_INTERPRET=1, which takes any shape: parity tests),
    no live multi-device mesh (GSPMD cannot partition a Mosaic call,
    and this one has no shard_map island), and the shape: a page is
    one [BS, Hkv*D] tile (`num_kv_heads`, by default the query's
    heads), so Hkv*D must fill whole 128-lane rows and BS whole
    sublane groups, and the query heads whole groups of K/V heads.
    (The latent kernel's page is one [BS, row] tile: its runner asks
    with one head of `row` lanes.)"""
    from . import _on_tpu, _partitioned, interpret_mode

    kv_heads = num_kv_heads or num_heads
    if num_heads % kv_heads:
        return False
    if not _on_tpu():
        return interpret_mode()
    return not _partitioned() and (kv_heads * head_dim) % 128 == 0 \
        and block_size % 8 == 0


def _head_selector(h, d):
    """SEL [H, H*D] f32: row h is 1 on head h's D lanes, 0 elsewhere."""
    return jnp.repeat(jnp.eye(h, dtype=jnp.float32), d, axis=1)


_TILE_BYTES = 1024 * 1024


def _pages_per_group(block_size, row_bytes):
    """G: the pages of one sequence that are copied and multiplied
    together, for both kernels, from the shape: the largest power of
    two of rows whose tile (`row_bytes` a row, one pool's) fits in
    `_TILE_BYTES`, between 128 and 1024 rows, of at most 64 pages
    (the latent kernel's copies of a group are unrolled; PERF.md, PR
    33 and 42: the sweeps). A short row would otherwise
    pay a group's fixed cost (its waits, a loop step, a rescale of
    the softmax) every few bytes: K/V rows of 1 KB (4 heads of 128 in
    bf16) take 1024 rows, a latent row of 1280 B 512, GPT-2's f32
    K/V rows of 4 KB 256."""
    rows = max(128, min(1024, _TILE_BYTES // row_bytes))
    rows = 1 << (rows.bit_length() - 1)
    return max(1, min(64, rows // block_size))


_WINDOW_TILE_BYTES = 2 * 1024 * 1024


def _window_pages(window, block_size, row_bytes):
    """G of a call with a `window`, whose groups are counted from the
    page that holds the window's first key: the fewest pages that
    always hold a window's keys, `ceil(window / BS) + 1` (the first
    key may sit anywhere in its page), so that ONE group a sequence
    walks the whole window: 65 pages = 1040 rows for a window of 1024
    in pages of 16, where groups counted from position 0 straddle
    two 1024-row tiles. A window whose tile would pass
    `_WINDOW_TILE_BYTES` (2 MiB of one pool: the kernel's four tiles
    within half of the 16 MiB of VMEM a Mosaic kernel has by default
    on the v5e, where tiles of 512 rows of 4 KB run) takes the
    row-bytes rule of `_pages_per_group`, its groups still counted
    from the window's first page: at 1 KB rows a window up to 2032
    keys is one group, a longer one 1024-row groups."""
    pages = -(-window // block_size) + 1
    if pages * block_size * row_bytes > _WINDOW_TILE_BYTES:
        return _pages_per_group(block_size, row_bytes)
    return pages


def _first_copies(b, last, groups, start, parity_ref):
    """The opening of a grid step, for both kernels. `parity_ref`
    holds the parity of the groups walked so far: `base`, the tile
    (of two) this sequence's first group lands in. That group is on
    its way already where the sequence before had a last group to
    start it from; else its copies start here. `groups(i)` is the
    number of groups sequence i walks, from its group 0. Returns
    (base, that number for this sequence, the next sequence)."""
    @pl.when(b == 0)
    def _first():
        parity_ref[0] = 0

    base = parity_ref[0]
    n_groups = groups(b)
    before = jnp.maximum(b - 1, 0)
    after = jnp.minimum(b + 1, last)

    @pl.when((n_groups > 0) & ((b == 0) | (groups(before) == 0)))
    def _own_first():
        start(b, 0, base)

    return base, n_groups, after


def _next_copies(b, last, j, base, n_groups, after, groups, start):
    """Inside group j: the copies of group j + 1 (from a sequence's
    last group, of the next sequence's first) start into the other
    tile before this one is waited for. Returns this group's tile."""
    slot = (base + j) % 2

    @pl.when(j + 1 < n_groups)
    def _next_group():
        start(b, j + 1, 1 - slot)

    @pl.when((j + 1 == n_groups) & (b < last) & (groups(after) > 0))
    def _next_sequence():
        start(after, 0, 1 - slot)

    return slot


def _softmax_step(s, v, exact, acc_ref, m_ref, l_ref):
    """One group of the online softmax: scores s [rows, R] (masked
    to -inf past the context: p underflows to an exact zero)
    against values v [R, lanes]. Operands stay in the pool dtype,
    statistics f32 (the PR-8 rule): p is rounded to the pool dtype
    exactly as the reference rounds it before its PV product."""
    m_prev = m_ref[...]                                      # [rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                              keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, precision=exact,
        preferred_element_type=jnp.float32)


def _paged_kernel(tables_ref, lens_ref, q_ref, sel_ref, k_hbm, v_hbm,
                  o_ref, k_buf, v_buf, sems, acc_ref, m_ref, l_ref,
                  parity_ref, *, sm_scale, block_size, pages, num_q,
                  group=1, window=None):
    """One grid step: ONE sequence, its T query slots (decode is
    T = 1) against its live page groups, which the body walks itself
    (with a `window`, decode only, its groups counted from the page
    that holds the first of the `window` newest tokens, not from
    position 0: the pages before it are neither copied nor walked,
    and the rows before `context - window` are masked as the rows
    past the context are).
    Row t*H + h of every [T*H, ...] value is head h of slot t (with
    `group` G query heads a K/V head, `sel` has the Hkv K/V heads'
    rows and row (t*G + g)*Hkv + k is query head k*G + g of slot t:
    H below reads G*Hkv); the
    scratch holds the online-softmax state of those rows across the
    groups, the two [R, H*D] tiles a pool's pages are copied into
    (group j + 1 on its way while group j is multiplied), and the
    parity of the groups walked so far: the last group of a sequence
    starts the copies of the next sequence's first."""
    b = pl.program_id(0)
    last = pl.num_programs(0) - 1
    rows = pages * block_size
    sel = sel_ref[...]               # [H, H*D]
    heads = sel.shape[0]
    q_rows = num_q * group           # rows of q: a slot's G a K/V head

    def seen(i):
        # tokens visible to the deepest slot of sequence i: pages
        # wholly past them hold NULL_BLOCK padding for every slot and
        # are neither copied nor multiplied
        return lens_ref[i] + (num_q - 1)

    def end_page(i):
        return (seen(i) + (block_size - 1)) // block_size

    def origin(i):
        # with a `window`: the table column of the page that holds the
        # window's first key, where sequence i's group 0 starts
        return jnp.maximum(lens_ref[i] - window, 0) // block_size

    def groups(i):
        if window is None:
            return (seen(i) + (rows - 1)) // rows
        return (end_page(i) - origin(i) + (pages - 1)) // pages

    def first_page(i, j):
        # the table column of group j's first row
        return j * pages if window is None else origin(i) + j * pages

    def live_pages(i, j):
        # [lo, hi): the table columns of group j that hold a token
        # visible to a slot of sequence i: every other page is neither
        # copied nor waited for
        lo = first_page(i, j)
        return lo, jnp.minimum(lo + pages, end_page(i))

    def start(i, j, slot):
        # a rolled loop over the live pages: no branch a page, and the
        # body's text is one page's whatever the group's width
        def copy(p, carry):
            page = tables_ref[i, p]
            to = pl.ds(pl.multiple_of((p - first_page(i, j)) * block_size,
                                      block_size), block_size)
            for pool, buf, which in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                pltpu.make_async_copy(pool.at[page], buf.at[slot, to],
                                      sems.at[slot, which]).start()
            return carry

        jax.lax.fori_loop(*live_pages(i, j), copy, 0)

    def wait(i, j, slot):
        # the semaphore counts what arrived: the n live pages are
        # waited for as n's binary digits, one wait a pool for each
        # set bit (a whole group of 2^k pages: one)
        lo, hi = live_pages(i, j)
        for bit in range(pages.bit_length()):
            part = pl.ds(0, (1 << bit) * block_size)

            @pl.when(((hi - lo) >> bit) % 2 == 1)
            def _part():
                for buf, which in ((k_buf, 0), (v_buf, 1)):
                    pltpu.make_async_copy(buf.at[slot, part],
                                          buf.at[slot, part],
                                          sems.at[slot, which]).wait()

    base, n_groups, after = _first_copies(b, last, groups, start,
                                          parity_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    ctx = lens_ref[b]                # tokens visible to query slot 0
    if num_q > 1:                    # [T*H, 1]: slot t sees t more
        row = jax.lax.broadcasted_iota(
            jnp.int32, (q_rows * heads, 1), 0)
        ctx = ctx + sum((row >= t * group * heads).astype(jnp.int32)
                        for t in range(1, num_q))
    # head h's query on its own D lanes of row h, zeros on the others
    # (a 0/1 mask, exact in any dtype): ONE product over all H*D
    # lanes gives every head's scores. They run on the pool's dtype
    # (bf16-native MXU; an f32 pool keeps f32 scores)
    q = q_ref[0].astype(jnp.float32)                         # [T, H*D]
    qx = jnp.concatenate([q[t:t + 1] * sel for t in range(q_rows)],
                         axis=0).astype(k_buf.dtype)         # [T*H, H*D]
    # an f32 pool is multiplied as f32: left to itself Mosaic rounds
    # f32 operands to one bf16 pass (2e-3 of error on the v5e where
    # the dense reference, a VPU reduction there, has 6e-7; PR 30),
    # which the configuration's precision is not
    exact = jax.lax.Precision.HIGHEST \
        if k_buf.dtype == jnp.float32 else None

    def first_row(j):
        # the position of row 0 of group j's tile in the sequence
        return j * rows if window is None \
            else first_page(b, j) * block_size

    def group(j, carry):
        slot = _next_copies(b, last, j, base, n_groups, after, groups,
                            start)
        wait(b, j, slot)
        k = k_buf[slot]                                      # [R, H*D]
        # rows no copy wrote hold what the tile held before: masked
        # out of the scores below, and zeroed here so that a zero
        # weight meets a zero
        v_pos = first_row(j) + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0)
        live = v_pos < seen(b)
        if window is not None:
            live &= v_pos >= ctx - window
        v = jnp.where(live, v_buf[slot], 0)
        s = jax.lax.dot_general(
            qx, k, (((1,), (1,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32) * sm_scale   # [T*H, R]
        k_pos = first_row(j) + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        # positions past the context (for the shallower slots, past
        # theirs) mask to -inf: p underflows to an exact zero
        seen_k = k_pos < ctx
        if window is not None:
            seen_k &= k_pos >= ctx - window
        s = jnp.where(seen_k, s, _NEG_INF)
        # every row meets every head's lanes here; the end keeps
        # its own
        _softmax_step(s, v, exact, acc_ref, m_ref, l_ref)
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    parity_ref[0] = (base + n_groups) % 2

    o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)        # [T*H, H*D]
    for t in range(q_rows):
        o_ref[0, t:t + 1] = jnp.sum(
            o[t * heads:(t + 1) * heads] * sel, axis=0,
            keepdims=True).astype(o_ref.dtype)


def _whole_groups(block_tables, pages):
    """The tables as int32, as wide as whole groups of `pages`: a
    width G does not divide gets its last column again, at positions
    no context reaches."""
    tables = jnp.asarray(block_tables, jnp.int32)
    short = -tables.shape[1] % pages
    if short:
        tables = jnp.pad(tables, ((0, 0), (0, short)), mode="edge")
    return tables


def _paged_call(q, k_pool, v_pool, block_tables, context_lens, sm_scale,
                interpret, window=None):
    """q [B, T, H, D] through the kernel: grid (B,), the tables and
    lengths as SCALAR PREFETCH arguments, the pools left where they
    are (`pl.ANY`): the body copies the pages it needs itself."""
    b, t, hq, d = q.shape
    if window is not None and (t > 1 or window < 1):
        raise ValueError(f"a window ({window}) is one query token's")
    n, bs, h, dk = k_pool.shape
    if dk != d or hq % h:
        raise ValueError(f"pool heads/dim {(h, dk)} under query "
                         f"{(hq, d)}")
    hd = h * d
    row_bytes = hd * k_pool.dtype.itemsize
    if window is None:
        pages = _pages_per_group(bs, row_bytes)
        tables = _whole_groups(block_tables, pages)
    else:
        # a windowed walk reads no column past the context's last page
        pages = _window_pages(window, bs, row_bytes)
        tables = jnp.asarray(block_tables, jnp.int32)
    _cmon.stat_add(f"kernels/paged/rows_{pages * bs}", 1)
    group = hq // h
    kernel = functools.partial(
        _paged_kernel, sm_scale=sm_scale, block_size=bs, pages=pages,
        num_q=t, group=group,
        **({} if window is None else {"window": int(window)}))
    if group > 1:
        # the G query heads of a K/V head as G rows a slot over the
        # pool's own lanes: [B, T, Hkv, G, D] -> [B, T*G, Hkv*D]
        q = q.reshape(b, t, h, group, d).swapaxes(2, 3)
        t = t * group
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, t, hd), lambda i, bt, cl: (i, 0, 0)),
            pl.BlockSpec((h, hd), lambda i, bt, cl: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, t, hd), lambda i, bt, cl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages * bs, hd), k_pool.dtype),
            pltpu.VMEM((2, pages * bs, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((t * h, hd), jnp.float32),
            pltpu.VMEM((t * h, 1), jnp.float32),
            pltpu.VMEM((t * h, 1), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, t, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(tables, jnp.asarray(context_lens, jnp.int32),
      q.reshape(b, t, hd), _head_selector(h, d),
      k_pool.reshape(n, bs, hd), v_pool.reshape(n, bs, hd))
    if group > 1:
        return out.reshape(b, t // group, group, h, d).swapaxes(
            2, 3).reshape(b, t // group, hq, d)
    return out.reshape(b, t, h, d)


def paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                    sm_scale=1.0, interpret=False, window=None):
    """Ragged paged-attention decode: one launch, all sequences.
    `window` (static; None = the whole context): the query sees the
    `window` newest tokens, itself included, and the walk starts at
    the page that holds the first of them, in groups as wide as a
    window (`_window_pages`): table columns before `(context -
    window) // BS` are never read (a cache that freed those blocks
    leaves NULL there)."""
    return _paged_call(q[:, None], k_pool, v_pool, block_tables,
                       context_lens, sm_scale, interpret, window)[:, 0]


def _gather_context(pool, block_tables):
    """pool [N, BS, H, D] -> every sequence's context [B, MAXB*BS, H,
    D] in table order. Blocks are gathered as whole [BS, H*D] rows,
    the pools' own minor dimension (`kv_cache`), so a pool handed in
    as a view of that form is read where it lies."""
    n, bs, h, d = pool.shape
    b, maxb = block_tables.shape
    return pool.reshape(n, bs, h * d)[block_tables].reshape(
        b, maxb * bs, h, d)


def _seen(n_rows, context_lens, window):
    """[B, rows]: the gathered rows one query token a sequence sees."""
    pos = jnp.arange(n_rows)[None, :]
    mask = pos < context_lens[:, None]
    if window is not None:
        mask &= pos >= context_lens[:, None] - window
    return mask


def paged_attention_reference(q, k_pool, v_pool, block_tables,
                              context_lens, sm_scale=1.0, window=None):
    """Dense gather reference — the math the kernel must match, and
    the engine's CPU fallback. Mirrors the training `_attention`
    softmax exactly (f32 scores, -1e30 mask, softmax, cast, PV) so a
    paged decode step reproduces the full re-forward loop's tokens.
    With a `window` the rows before `context - window` are masked
    too (whatever their table columns name)."""
    seq_k = _gather_context(k_pool, block_tables)
    seq_v = _gather_context(v_pool, block_tables)
    if q.shape[1] != seq_k.shape[2]:
        # grouped K/V heads: query head k*G + g reads K/V head k,
        # the same softmax with the group as one more axis
        b, hq, d = q.shape
        qg = q.reshape(b, seq_k.shape[2], -1, d)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, seq_k,
                       preferred_element_type=jnp.float32) * sm_scale
        mask = _seen(seq_k.shape[1], context_lens, window)
        s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bkgs,bskd->bkgd", p, seq_v).reshape(b, hq, d)
    s = jnp.einsum("bhd,bshd->bhs", q, seq_k,
                   preferred_element_type=jnp.float32) * sm_scale
    mask = _seen(seq_k.shape[1], context_lens, window)
    s = jnp.where(mask[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bshd->bhd", p, seq_v)


# ---------------------------------------------------------------------------
# multi-query decode slots (speculative-decode verification)
# ---------------------------------------------------------------------------

def paged_attention_multi(q, k_pool, v_pool, block_tables,
                          context_lens, sm_scale=1.0,
                          interpret=False):
    """Multi-query ragged paged-attention: q [B, T, H, D], slot t of
    sequence b attends `context_lens[b] + t` tokens (per-slot causal
    masking over the SAME block table). One launch verifies a whole
    speculative window; T must be small (the kernel's state and its
    score tile grow with T*H rows)."""
    t = q.shape[1]
    if t > 8:
        raise ValueError(
            f"paged_attention_multi holds T*H rows of state - T={t} "
            "query slots > 8 would bloat the kernel; use the dense "
            "reference for long windows")
    return _paged_call(q, k_pool, v_pool, block_tables, context_lens,
                       sm_scale, interpret)


def paged_attention_multi_reference(q, k_pool, v_pool, block_tables,
                                    context_lens, sm_scale=1.0):
    """Dense multi-query reference: the verify math the kernel must
    match, the engine's CPU fallback, AND the prefix-cache tail
    prefill's attention (slot t at absolute position
    context_lens[b] - 1 + t sees context_lens[b] + t tokens — the
    same convention for both uses)."""
    seq_k = _gather_context(k_pool, block_tables)
    seq_v = _gather_context(v_pool, block_tables)
    t = q.shape[1]
    s = jnp.einsum("bthd,bshd->bths", q, seq_k,
                   preferred_element_type=jnp.float32) * sm_scale
    pos = jnp.arange(seq_k.shape[1])[None, None, :]
    ctx = context_lens[:, None, None] \
        + jnp.arange(t)[None, :, None]     # [B, T, 1]
    mask = pos < ctx                       # [B, T, S]
    s = jnp.where(mask[:, :, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bths,bshd->bthd", p, seq_v)


# ---------------------------------------------------------------------------
# latent (MLA) rows: one pool, one shared head, keys that are the values
# ---------------------------------------------------------------------------

def _latent_kernel(tables_ref, lens_ref, q_ref, pool_hbm, o_ref, buf,
                   sems, acc_ref, m_ref, l_ref, parity_ref, *, sm_scale,
                   block_size, pages):
    """One grid step: ONE sequence, its H query rows [Hp, row] (the
    absorbed query, `q_lat | q_rope`, zeros to the stored width)
    against its live page groups. A group's [R, row] tile is BOTH
    operands: `s = q . tile^T`, `acc += p . tile`, so one copy of a
    page serves scores and values and every head reads the whole
    row. Every page of a live group is copied, the table's NULL and
    padded columns too (whole tiles: no branch a page, ONE wait a
    group), and the rows past the context are masked in the scores
    and zeroed as values."""
    b = pl.program_id(0)
    last = pl.num_programs(0) - 1
    rows = pages * block_size

    def groups(i):
        return (lens_ref[i] + (rows - 1)) // rows

    def start(i, j, slot):
        for g in range(pages):
            pltpu.make_async_copy(
                pool_hbm.at[tables_ref[i, j * pages + g]],
                buf.at[slot, pl.ds(g * block_size, block_size)],
                sems.at[slot]).start()

    def wait(slot):
        # the semaphore counts what arrived: one wait for the tile
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sems.at[slot]).wait()

    base, n_groups, after = _first_copies(b, last, groups, start,
                                          parity_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    ctx = lens_ref[b]
    q = q_ref[0]                                             # [Hp, row]
    exact = jax.lax.Precision.HIGHEST \
        if buf.dtype == jnp.float32 else None

    def group(j, carry):
        slot = _next_copies(b, last, j, base, n_groups, after, groups,
                            start)
        wait(slot)
        tile = buf[slot]                                     # [R, row]
        live = j * rows + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) < ctx
        s = jax.lax.dot_general(
            q, tile, (((1,), (1,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32) * sm_scale   # [Hp, R]
        k_pos = j * rows + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < ctx, s, _NEG_INF)
        _softmax_step(s, jnp.where(live, tile, 0), exact, acc_ref, m_ref,
                      l_ref)
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    parity_ref[0] = (base + n_groups) % 2
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


def paged_latent_attention(q, pool, block_tables, context_lens,
                           sm_scale=1.0, interpret=False):
    """Absorbed latent attention through the block tables: q
    [B, H, row] (one query token a sequence, in the pool's row
    width), pool [N, BS, row] whose rows are key and value to every
    head -> softmax(q . rows^T * sm_scale) . rows, [B, H, row] in
    q's dtype: the caller keeps the lanes that are values (the
    first `kv_lora_rank`). The heads are padded to the dtype's
    sublane tile (20 -> 32 in bf16)."""
    b, h, row = q.shape
    _, bs, row_p = pool.shape
    if row_p != row:
        raise ValueError(f"pool row {row_p} != query row {row}")
    q = q.astype(pool.dtype)
    tile = 32 // pool.dtype.itemsize
    hp = -(-h // tile) * tile
    q = jnp.pad(q, ((0, 0), (0, hp - h), (0, 0)))
    pages = _pages_per_group(bs, row * pool.dtype.itemsize)
    kernel = functools.partial(
        _latent_kernel, sm_scale=sm_scale, block_size=bs, pages=pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hp, row), lambda i, bt, cl: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hp, row), lambda i, bt, cl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages * bs, row), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((hp, row), jnp.float32),
            pltpu.VMEM((hp, 1), jnp.float32),
            pltpu.VMEM((hp, 1), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hp, row), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(_whole_groups(block_tables, pages),
      jnp.asarray(context_lens, jnp.int32), q, pool)
    return out[:, :h]
