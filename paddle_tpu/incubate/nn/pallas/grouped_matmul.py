"""Pallas TPU grouped matmul: the routed experts' two products.

    out[rows of group g] = rows[rows of group g] @ w[first_group + g]

`rows [M, K]` lie sorted by group, `sizes [E]` says how many each of
the E groups holds (data; the shapes are static), `w [G, K, N]` holds
one matrix a group, `G >= first_group + E`: a layer's experts, or
every layer's stacked `[L*E, K, N]` with `first_group = layer * E` (a
traced scalar: the stack is never sliced, `moe/dropless.py`). Rows
behind the last group come back zero. bf16 operands, float32
accumulation, the result in the operands' dtype: what
`jax.lax.ragged_dot` gives, which stays the implementation wherever
`grouped_matmul_supported` says no.

Why a kernel of the repo's own (PERF.md, PR 35): XLA's `ragged-dot`
multiplies every group as a 512-row tile (`ragged_dot_tiling=
"512,512,512"` in the compiled HLO), so a decode step's groups of
1-16 rows cost 32x their flops and the experts' weights stream at 38
% of the HBM's speed. A grouped matmul at decode is a READ of the hit
experts' weights; everything here serves that read.

Grid `(N / tn, E)`, group-major and weights-stationary: step `(j, e)`
holds ONE `[K, tn]` block of ONE hit expert, brought by the pipeline
(the next block is on its way while this one is multiplied), and
every hit expert's every block is read exactly once whatever the
routing's skew. What keeps dead work off the grid, as the paged
kernels walk live page groups only:

- the groups that hold rows are compacted in front (`hit`, a scalar
  prefetch argument); the steps behind the last of them name the
  block the last live step named, which the pipeline therefore does
  not copy again, and their body is skipped: an empty group (every
  other layer's in the stacked view, an expert no token chose) costs
  one idle grid step and no byte;
- the rows and a column block of the result stay in VMEM across the
  groups (`[M, K]` and `[M, tn]`, the same block at every step), so
  the body reads a group's rows and writes its results where they
  lie, with no copy of its own.

The body walks the group's rows in windows of `tm`: a window starts
at a multiple of 16 rows (a bf16 sublane tile; the group's own start
is anywhere), is multiplied whole, and the rows of it that are the
group's are kept (a select against what the block holds: a window
shares its first and last tile with the neighbours). `tm` follows
the rows a group holds (`_tiles`): a 128x128 tile of weights occupies
the MXU about as long for 16 rows as for 128, so any window up to
128 rows leaves the product under the time of the weights' bytes,
and a window as high as the group needs each tile of weights pushed
once.

`interpret=True` runs the same kernel through the Pallas interpreter
(CPU parity tests, any shape).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "grouped_matmul_supported"]

_ALIGN = 16                      # rows of a bf16 sublane tile
_WEIGHT_BLOCK_BYTES = 8 << 20    # one [K, tn] block; two are in flight
_VMEM_BYTES = 96 << 20           # of a v5e's 128 MiB
_CHUNK = 512                     # columns one `dot` of the body makes


@functools.lru_cache(maxsize=None)
def _tiles(m, groups, k, n, itemsize):
    """(tm, tn) from the static shape, or None where the blocks do
    not fit. `tm`: twice the rows a group holds on average (the
    fullest expert of a decode step takes about twice the mean, and a
    group's start is not aligned), as a power of two between 16 and
    128 rows. `tn`: the widest whole split of N into 128-lane columns
    whose `[K, tn]` block stays within `_WEIGHT_BLOCK_BYTES` (a few
    large copies an expert, not many small ones) and leaves room in
    VMEM for the rows and the result's block: a decode step's
    thousand rows take half an expert a block, a 2048-token
    prefill's 8192 take 1024 columns, and rows that do not fit beside
    any block (LongCat's 24 576 of 6144 values) get None."""
    if m % _ALIGN or n % 128 or k % 128:
        return None
    want = max(_ALIGN, min(128, 2 * -(-m // groups)))
    tm = min(1 << (want - 1).bit_length(), m)
    lanes = n // 128
    for d in range(lanes, 0, -1):
        tn = d * 128
        if lanes % d == 0 and k * tn * itemsize <= _WEIGHT_BLOCK_BYTES \
                and _vmem_bytes(m, k, tm, tn, itemsize) <= _VMEM_BYTES:
            return tm, tn
    return None


def _vmem_bytes(m, k, tm, tn, itemsize):
    """What a call holds in VMEM: the rows once (their block never
    changes: one buffer), two copies (the pipeline's) of a block of
    weights and of a column block of the result, the body's float32
    window, and room for the compiler's own."""
    return itemsize * (m * k + 2 * k * tn + 2 * m * tn) \
        + 4 * tm * (min(tn, _CHUNK) + k) + (8 << 20)


def grouped_matmul_supported(m, groups, k, n, dtype):
    """Do the experts' products of this shape run in the kernel here?
    Answered from what the code can observe, with no switch of its
    own (as `paged_decode_supported`): the platform (a TPU; on the
    CPU only the interpreter, PADDLE_PALLAS_INTERPRET=1, which takes
    any shape: parity tests), no live multi-device mesh (GSPMD cannot
    partition a Mosaic call, and this one has no shard_map island),
    and the static shape: bf16 operands, whole tiles, and rows and
    result blocks that fit in VMEM beside the weights' (`_tiles`)."""
    from . import _on_tpu, _partitioned, interpret_mode

    if not _on_tpu():
        return interpret_mode()
    return not _partitioned() and jnp.dtype(dtype) == jnp.bfloat16 \
        and _tiles(m, groups, k, n, 2) is not None


def _kernel(hit_ref, starts_ref, meta_ref, rows_ref, w_ref, o_ref, *, tm):
    """Grid step (j, e): the e-th group that holds rows, its `[K, tn]`
    block `w_ref` against its rows, window by window."""
    e = pl.program_id(1)
    m, tn = o_ref.shape

    @pl.when(e == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(e < meta_ref[0])
    def _live():
        g = hit_ref[e]
        start, end = starts_ref[g], starts_ref[g + 1]
        first = (start // _ALIGN) * _ALIGN

        def window(i, carry):
            # the last window is pulled back inside the rows; what it
            # computes twice is kept once, by the select
            a = pl.multiple_of(jnp.minimum(first + i * tm, m - tm),
                               _ALIGN)
            at = pl.ds(a, tm)
            row = a + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
            mine = (row >= start) & (row < end)
            lhs = rows_ref[at, :]
            for c in range(0, tn, _CHUNK):
                cols = slice(c, min(c + _CHUNK, tn))
                acc = jnp.dot(lhs, w_ref[:, cols],
                              preferred_element_type=jnp.float32)
                o_ref[at, cols] = jnp.where(
                    mine, acc.astype(o_ref.dtype), o_ref[at, cols])
            return carry

        jax.lax.fori_loop(0, (end - first + tm - 1) // tm, window, 0)


@functools.partial(jax.custom_jvp, nondiff_argnums=(4, 5))
def _launch(rows, w, sizes, first_group, tiles, interpret):
    m, k = rows.shape
    n = w.shape[-1]
    e = sizes.shape[0]
    tm, tn = tiles
    # the groups that hold rows, in front; behind them the last of
    # them again: the same block, no copy, a skipped body
    live = sizes > 0
    n_hit = live.sum().astype(jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    hit = jnp.where(jnp.arange(e) < n_hit, order,
                    order[jnp.maximum(n_hit - 1, 0)])
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(sizes, dtype=jnp.int32)])
    meta = jnp.stack([n_hit, jnp.asarray(first_group, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, e),
        in_specs=[
            pl.BlockSpec((m, k), lambda j, i, hit, starts, meta: (0, 0),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((None, k, tn), lambda j, i, hit, starts, meta:
                         (meta[1] + hit[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((m, tn),
                               lambda j, i, hit, starts, meta: (0, j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="grouped_matmul",
    )(hit, starts, meta, rows, w)


@_launch.defjvp
def _no_derivative(tiles, interpret, primals, tangents):
    raise NotImplementedError(
        "grouped_matmul has no derivative: the dropless experts are "
        "a serving path (ROADMAP R1); train through MoELayer")


# a jit of its own: the call sites of one program that share a shape
# (LFM2's eight unrolled expert layers) share ONE traced and lowered
# kernel, not a Mosaic module each (0.2-0.3 s of every program's
# first call on the chip's host, cache hit or not)
_call = jax.jit(_launch, static_argnums=(4, 5))


def grouped_matmul(rows, w, sizes, first_group=0, interpret=False):
    """rows [M, K] sorted by group, w [G, K, N], sizes [E] int32 (E
    groups from `first_group` of the G) -> [M, N] in rows' dtype;
    rows behind the last group are zero. Under the interpreter any
    shape is taken (the rows padded to whole windows); compiled, the
    shape is one `grouped_matmul_supported` admits."""
    m, k = rows.shape
    n = w.shape[-1]
    e = sizes.shape[0]
    w = w.astype(rows.dtype)
    sizes = sizes.astype(jnp.int32)
    tiles = _tiles(m, e, k, n, rows.dtype.itemsize)
    if tiles is None:
        if not interpret:
            raise ValueError(
                f"grouped_matmul: no tiles for rows {rows.shape}, "
                f"weights {w.shape}; ask grouped_matmul_supported")
        # toy shapes: whole windows of rows, the columns in one block
        pad = -m % _ALIGN
        out = _call(jnp.pad(rows, ((0, pad), (0, 0))), w, sizes,
                    first_group, (_ALIGN, n), True)
        return out[:m]
    return _call(rows, w, sizes, first_group, tiles, interpret)
