"""SUMMA distributed matmul over the Fleet mesh (arxiv 2112.09017).

C = A @ B with A (M,K), B (K,N), C (M,N) all in the `blocks` layout
P(rx, cx) on a px x py grid. The classic panel loop: for each inner
panel of width nb, the grid column owning A's panel broadcasts it
along the rows (mesh axis cx) and the grid row owning B's panel
broadcasts it along the columns (mesh axis rx); every rank then
accumulates one local (M/px, nb) @ (nb, N/py) MXU matmul. Per-rank
comm volume is T * (M/px + N/py) * nb elements of broadcast — priced
by the existing comm/broadcast/{calls,bytes} counters at trace time.

Block size: PADDLE_LINALG_BLOCK pins it; PADDLE_LINALG_AUTOTUNE=1
profiles candidate programs through cost_model.CostModel (whose
compiles ride the persistent compile cache, so a repeated sweep is
warm); otherwise the largest divisor of gcd(K/px, K/py) capped at
DEFAULT_BLOCK_CAP.
"""
from __future__ import annotations

import math
import os

import jax.numpy as jnp
from jax import lax

from . import runtime
from .sharded import ShardedMatrix

__all__ = ["matmul", "choose_block_size", "block_candidates"]

DEFAULT_BLOCK_CAP = 256

# chosen block size per (grid sig, M, K, N, dtype) — one autotune
# sweep per shape family
_chosen: dict = {}

_cost_model = None


def _cost():
    global _cost_model
    if _cost_model is None:
        from ...cost_model import CostModel

        _cost_model = CostModel()
    return _cost_model


def block_candidates(K, grid_, cap=DEFAULT_BLOCK_CAP):
    """Valid SUMMA panel widths: divisors of gcd(K/px, K/py), largest
    first, capped (a panel wider than the cap stops paying off and
    inflates the broadcast working set)."""
    g = runtime.block_divisor(K, grid_.px, grid_.py)
    if g <= 0:
        raise ValueError(
            f"paddle.linalg.dist.matmul: inner dim {K} is not "
            f"divisible by the {grid_.px}x{grid_.py} grid")
    divs = [d for d in range(1, g + 1) if g % d == 0 and d <= cap]
    return sorted(divs, reverse=True)


def _build(grid_, M, K, N, nb, dtype):
    """The traceable SUMMA island for one shape/block choice."""
    px, py = grid_.px, grid_.py
    ka, kb = K // py, K // px  # A / B inner extents per rank

    def body(a, b):
        acc = jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)
        for t in range(K // nb):
            g0 = t * nb
            a_pan = lax.slice_in_dim(a, g0 % ka, g0 % ka + nb, axis=1)
            b_pan = lax.slice_in_dim(b, g0 % kb, g0 % kb + nb, axis=0)
            # owner column of A's panel broadcasts along the row;
            # owner row of B's panel broadcasts along the column
            a_pan = runtime.bcast(a_pan, grid_.row_axes(), g0 // ka)
            b_pan = runtime.bcast(b_pan, grid_.col_axes(), g0 // kb)
            acc = acc + jnp.matmul(
                a_pan, b_pan, preferred_element_type=jnp.float32)
        return acc.astype(dtype)

    spec = grid_.block_spec()

    def fn(a, b):
        return runtime.shard_map(body, grid_.mesh,
                                 (spec, spec), spec)(a, b)

    return fn


def choose_block_size(a: ShardedMatrix, b: ShardedMatrix,
                      candidates=None, max_probes=3):
    """The SUMMA panel width for this (shapes, grid) pairing.

    Precedence: PADDLE_LINALG_BLOCK (validated against the candidate
    set) > cached autotune result > PADDLE_LINALG_AUTOTUNE=1 profile
    sweep over up to `max_probes` candidates via CostModel
    > largest capped divisor."""
    grid_ = a.grid
    K = a.shape[1]
    cands = (list(candidates) if candidates
             else block_candidates(K, grid_))
    env = os.environ.get("PADDLE_LINALG_BLOCK")
    if env:
        nb = int(env)
        if nb not in block_candidates(K, grid_, cap=K):
            raise ValueError(
                f"PADDLE_LINALG_BLOCK={nb} does not divide "
                f"gcd(K/px, K/py) for K={K} on {grid_} (valid: "
                f"divisors of "
                f"{runtime.block_divisor(K, grid_.px, grid_.py)})")
        return nb
    key = (grid_.sig(), a.shape, b.shape, str(a.dtype))
    if key in _chosen:
        return _chosen[key]
    if os.environ.get("PADDLE_LINALG_AUTOTUNE", "0") != "1" \
            or len(cands) == 1:
        return cands[0]
    # spread probes across the candidate range (largest, middle,
    # smallest) — adjacent divisors measure within noise of each other
    probes = sorted({cands[0], cands[len(cands) // 2], cands[-1]},
                    reverse=True)[:max_probes]
    M, N = a.shape[0], b.shape[1]
    best, best_t = probes[0], math.inf
    for nb in probes:
        fn = _build(grid_, M, K, N, nb, a.dtype)
        t = _cost().profile_measure(fn, a.value, b.value,
                                    warmup=1, iters=2)
        if t < best_t:
            best, best_t = nb, t
    _chosen[key] = best
    return best


def matmul(a: ShardedMatrix, b: ShardedMatrix,
           block_size=None) -> ShardedMatrix:
    """Distributed C = A @ B (SUMMA). Both operands must share the
    grid and the `blocks` layout; the result lands in the same
    layout."""
    if not isinstance(a, ShardedMatrix) or \
            not isinstance(b, ShardedMatrix):
        raise TypeError(
            "paddle.linalg.dist.matmul expects two ShardedMatrix "
            f"operands, got ({type(a).__name__}, {type(b).__name__})")
    if a.grid.sig() != b.grid.sig():
        raise ValueError(
            "paddle.linalg.dist.matmul: operands live on different "
            f"grids ({a.grid} vs {b.grid})")
    if a.layout != "blocks" or b.layout != "blocks":
        raise ValueError(
            "paddle.linalg.dist.matmul needs the 'blocks' layout "
            f"(got {a.layout!r} @ {b.layout!r})")
    M, K = a.shape
    K2, N = b.shape
    if K != K2:
        raise ValueError(
            f"paddle.linalg.dist.matmul: inner dims differ — "
            f"A {a.shape} @ B {b.shape}")
    grid_ = a.grid
    if N % grid_.py or M % grid_.px or K % grid_.px or K % grid_.py:
        raise ValueError(
            "paddle.linalg.dist.matmul: shapes "
            f"{a.shape} @ {b.shape} do not tile the "
            f"{grid_.px}x{grid_.py} grid")
    nb = int(block_size) if block_size else choose_block_size(a, b)
    if (K // grid_.py) % nb or (K // grid_.px) % nb:
        raise ValueError(
            f"paddle.linalg.dist.matmul: block_size {nb} must divide "
            f"gcd(K/px, K/py) = "
            f"{runtime.block_divisor(K, grid_.px, grid_.py)}")
    label = f"summa_{M}x{K}x{N}_nb{nb}_{a.dtype}"
    compiled = runtime.compile_program(
        label, lambda: _build(grid_, M, K, N, nb, a.dtype),
        grid_, (a.value, b.value))
    out = runtime.dispatch("matmuls", label, compiled,
                           (a.value, b.value))
    return ShardedMatrix(out, grid_, layout="blocks", _validated=True)
