"""Blockwise quantize/dequantize kernels for quantized collectives.

EQuARX-style (PAPERS.md, arxiv 2506.17615) blockwise compression of a
flat f32 communication buffer: the buffer is viewed as (nblocks, B)
rows — B contiguous elements per scale block, the same
flatten/pad/concat discipline as the PR-8 fused-optimizer packers
(incubate/nn/pallas/optim.py) — and each block carries ONE f32
abs-max scale:

    int8  codes = round(x / (absmax/127)) in [-127, 127]   (1 B/elem)
    fp8   codes = f8e4m3(x / (absmax/448)) on a bf16 wire
          carrier (2 B/elem — XLA collectives on every backend move
          bf16; the e4m3 cast is the lossy step, the carrier is not)

Two implementations with BIT-IDENTICAL semantics, test-gated against
each other in interpret mode (tests/test_comm_compress.py):

  * `*_ref` — plain jnp, runs anywhere (this is what compiled train
    steps use on CPU and whenever PADDLE_PALLAS_FUSION is off);
  * Pallas TPU kernels behind PADDLE_PALLAS_FUSION=1 (+
    PADDLE_PALLAS_INTERPRET=1 on CPU), grid over groups of
    `_ROWS` scale blocks (the int8 tile is (32, 128), and Mosaic
    refuses a (1, B) block of an (nblocks, B) array). int8 only —
    the f8e4m3 cast stays on the jnp path.

A zero block (absmax 0) gets scale 1.0 so the codes are exactly 0 and
dequantize returns exactly 0 — padding is bit-neutral through the
whole pipeline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["quantize_blocks", "dequantize_blocks", "quantize_ref",
           "dequantize_ref", "wire_dtype", "wire_itemsize",
           "INT8_QMAX", "FP8_MAX"]

INT8_QMAX = 127.0
FP8_MAX = 448.0  # float8_e4m3fn finite max


def wire_dtype(mode):
    """The dtype that actually crosses the wire for a compress mode."""
    if mode == "int8":
        return jnp.int8
    if mode == "fp8":
        return jnp.bfloat16  # e4m3 values on a bf16 carrier
    return jnp.float32


def wire_itemsize(mode):
    return jnp.dtype(wire_dtype(mode)).itemsize


def _as_blocks(flat, block):
    n = flat.shape[-1] if flat.ndim else flat.size
    total = int(flat.size)
    if total % block:
        raise ValueError(
            f"compress: buffer of {total} elements is not a multiple "
            f"of the scale block ({block}) — pack/pad upstream")
    del n
    return flat.reshape(-1, block)


def _block_scales(xb, qmax):
    amax = jnp.max(jnp.abs(xb), axis=-1)
    return jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)


def quantize_ref(flat, block, mode):
    """flat f32 (any shape, size % block == 0) -> (codes, scales).
    codes: wire-dtype array of flat's shape; scales: f32 (size/block,).
    """
    shape = flat.shape
    xb = _as_blocks(flat.astype(jnp.float32), block)
    if mode == "int8":
        s = _block_scales(xb, INT8_QMAX)
        q = jnp.clip(jnp.round(xb / s[:, None]), -INT8_QMAX,
                     INT8_QMAX).astype(jnp.int8)
    elif mode == "fp8":
        s = _block_scales(xb, FP8_MAX)
        q = (xb / s[:, None]).astype(jnp.float8_e4m3fn) \
            .astype(jnp.bfloat16)
    else:
        raise ValueError(f"compress: unknown quantize mode {mode!r}")
    return q.reshape(shape), s


def dequantize_ref(codes, scales, block, mode):
    """Inverse of quantize_ref: wire codes + per-block scales -> f32
    of codes' shape."""
    shape = codes.shape
    qb = _as_blocks(codes, block).astype(jnp.float32)
    out = qb * scales.reshape(-1, 1)
    del mode
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# Pallas int8 kernels (one grid step == _ROWS scale blocks)
# ---------------------------------------------------------------------------

_ROWS = 32  # scale blocks per grid step: one int8 (32, 128) tile row


def _quant_i8_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]                                     # [R, B]
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # [R, 1]
    scale = jnp.where(amax > 0, amax / INT8_QMAX, 1.0)
    s_ref[...] = scale
    q_ref[...] = jnp.clip(jnp.round(x / scale), -INT8_QMAX,
                          INT8_QMAX).astype(jnp.int8)


def _dequant_i8_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _pad_rows(a, rows):
    """Zero rows up to a multiple of _ROWS: a zero block quantizes to
    scale 1 / codes 0 and dequantizes to 0, and is sliced off."""
    pad = -rows % _ROWS
    return jnp.pad(a, ((0, pad), (0, 0))) if pad else a


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _quantize_pallas_i8(flat, block, interpret):
    from jax.experimental import pallas as pl

    xb = _as_blocks(flat.astype(jnp.float32), block)
    nb = xb.shape[0]
    xb = _pad_rows(xb, nb)
    nb_pad = xb.shape[0]
    row = pl.BlockSpec((_ROWS, block), lambda i: (i, 0))
    scale = pl.BlockSpec((_ROWS, 1), lambda i: (i, 0))
    q, s = pl.pallas_call(
        _quant_i8_kernel,
        out_shape=(jax.ShapeDtypeStruct((nb_pad, block), jnp.int8),
                   jax.ShapeDtypeStruct((nb_pad, 1), jnp.float32)),
        grid=(nb_pad // _ROWS,),
        in_specs=[row],
        out_specs=(row, scale),
        interpret=interpret,
    )(xb)
    return q[:nb].reshape(flat.shape), s[:nb, 0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _dequantize_pallas_i8(codes, scales, block, interpret):
    from jax.experimental import pallas as pl

    qb = _as_blocks(codes, block)
    nb = qb.shape[0]
    qb = _pad_rows(qb, nb)
    nb_pad = qb.shape[0]
    row = pl.BlockSpec((_ROWS, block), lambda i: (i, 0))
    scale = pl.BlockSpec((_ROWS, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        _dequant_i8_kernel,
        out_shape=jax.ShapeDtypeStruct((nb_pad, block), jnp.float32),
        grid=(nb_pad // _ROWS,),
        in_specs=[row, scale],
        out_specs=row,
        interpret=interpret,
    )(qb, _pad_rows(scales.reshape(nb, 1), nb))
    return out[:nb].reshape(codes.shape)


def _use_pallas(mode, block):
    """The int8 kernels run where the fused library is armed and can
    run (a TPU, or the interpreter); compiled, the scale block must
    be lane-aligned."""
    if mode != "int8":
        return False
    from ...incubate.nn import pallas as _pallas

    if not _pallas.kernels_available():
        return False
    return block % 128 == 0 or not _pallas._on_tpu()


def quantize_blocks(flat, block, mode):
    """Dispatching entry: Pallas int8 kernel when the fused kernel
    library is armed (PADDLE_PALLAS_FUSION=1; interpret mode off-TPU),
    jnp reference otherwise. Same results either way."""
    if _use_pallas(mode, block):
        from ...incubate.nn import pallas as _pallas

        return _quantize_pallas_i8(
            flat, block,
            _pallas.interpret_mode() and not _pallas._on_tpu())
    return quantize_ref(flat, block, mode)


def dequantize_blocks(codes, scales, block, mode):
    if _use_pallas(mode, block):
        from ...incubate.nn import pallas as _pallas

        return _dequantize_pallas_i8(
            codes, scales, block,
            _pallas.interpret_mode() and not _pallas._on_tpu())
    return dequantize_ref(codes, scales, block, mode)
