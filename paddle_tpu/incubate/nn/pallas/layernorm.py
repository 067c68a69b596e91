"""Fused LayerNorm Pallas kernels (forward AND backward).

Replaces the reference's fused_bias_dropout_residual_layer_norm /
fused layernorm+activation CUDA epilogues
(paddle/fluid/operators/fused/fused_layernorm_residual_dropout_bias.h)
with TPU-native row-tiled kernels:

- `fused_layer_norm(x, w, b)`: LayerNorm over the last dim, optional
  GeLU epilogue (`activation="gelu"`) — the LayerNorm→GeLU pair the
  transformer FFN prologue wants as ONE activation read.
- `fused_residual_layer_norm(x, residual, w, b)`: residual-add →
  LayerNorm, returning BOTH the normalized output and the sum (the
  next block's residual) from one pass.

Statistics (mean / rstd) are computed in f32 and saved for the
backward, which recomputes x̂ from the saved sum — the standard
two-kernel LN autodiff, O(rows) extra memory. Rows are zero-padded to
the block multiple; zero rows contribute exactly nothing to dw/db and
their outputs are sliced off, so padding is bit-neutral.

`interpret=True` (or PADDLE_PALLAS_INTERPRET=1) runs the same kernels
through the Pallas interpreter so parity is testable on CPU, including
odd shapes no real TPU tiling would accept.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_layer_norm", "fused_residual_layer_norm"]

# per-row stats ride a small trailing lane dim (TPU tiling rule: block
# last dim == full array dim)
_STAT_LANES = 8

_MAX_BLOCK_ROWS = 256


def _row_block(n):
    """Row-block size: pow2 <= 256; tiny inputs shrink to the next
    pow2 >= n so padding never more than doubles the work."""
    if n >= _MAX_BLOCK_ROWS:
        return _MAX_BLOCK_ROWS
    return max(8, 1 << math.ceil(math.log2(max(1, n))))


def _pad_rows(a, n_pad):
    n = a.shape[0]
    if n == n_pad:
        return a
    return jnp.pad(a, ((0, n_pad - n), (0, 0)))


def _gelu(x, approximate):
    if approximate:
        # tanh form — matches jax.nn.gelu(approximate=True)
        c = math.sqrt(2.0 / math.pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _gelu_grad(x, approximate):
    if approximate:
        c = math.sqrt(2.0 / math.pi)
        u = c * (x + 0.044715 * x * x * x)
        t = jnp.tanh(u)
        du = c * (1.0 + 3.0 * 0.044715 * x * x)
        return 0.5 * (1.0 + t) + 0.5 * x * (1.0 - t * t) * du
    cdf = 0.5 * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    pdf = jnp.exp(-0.5 * x * x) / math.sqrt(2.0 * math.pi)
    return cdf + x * pdf


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, res_ref, w_ref, b_ref,
                   y_ref, s_ref, mu_ref, rs_ref, *,
                   eps, act, approx, has_residual):
    x = x_ref[...]
    if has_residual:
        # the sum happens in the INPUT dtype — identical rounding to
        # the unfused `x + residual` the composition performs, so the
        # fused path is numerics-compatible, not just close
        s = x + res_ref[...]
        s_ref[...] = s
    else:
        s = x
        # the placeholder sum output still must be written (an
        # undefined Mosaic output block is UB); every step hits the
        # same (1, H) block
        s_ref[...] = jnp.zeros_like(s_ref)
    sf = s.astype(jnp.float32)
    mu = jnp.mean(sf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(sf - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (sf - mu) * rstd
    y = xhat * w_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    if act == "gelu":
        y = _gelu(y, approx)
    y_ref[...] = y.astype(y_ref.dtype)
    mu_ref[...] = jnp.broadcast_to(mu, mu_ref.shape)
    rs_ref[...] = jnp.broadcast_to(rstd, rs_ref.shape)


def _ln_fwd_impl(x2, res2, w, b, eps, act, approx, interpret):
    n, h = x2.shape
    bn = _row_block(n)
    n_pad = ((n + bn - 1) // bn) * bn
    grid = n_pad // bn
    xp = _pad_rows(x2, n_pad)
    has_residual = res2 is not None
    rp = _pad_rows(res2, n_pad) if has_residual else \
        jnp.zeros((1, h), x2.dtype)  # placeholder, never read
    row_spec = pl.BlockSpec((bn, h), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    res_spec = row_spec if has_residual else pl.BlockSpec(
        (1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)
    wb_spec = pl.BlockSpec((1, h), lambda i: (0, 0),
                           memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((bn, _STAT_LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    kernel = functools.partial(_ln_fwd_kernel, eps=eps, act=act,
                               approx=approx, has_residual=has_residual)
    y, s, mu, rs = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((n_pad, h), x2.dtype),
                   jax.ShapeDtypeStruct((n_pad, h), x2.dtype)
                   if has_residual
                   else jax.ShapeDtypeStruct((1, h), x2.dtype),
                   jax.ShapeDtypeStruct((n_pad, _STAT_LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((n_pad, _STAT_LANES),
                                        jnp.float32)),
        grid=(grid,),
        in_specs=[row_spec, res_spec, wb_spec, wb_spec],
        out_specs=(row_spec,
                   row_spec if has_residual else wb_spec,
                   stat_spec, stat_spec),
        interpret=interpret,
    )(xp, rp, w.reshape(1, h), b.reshape(1, h))
    return y[:n], (s[:n] if has_residual else None), mu[:n], rs[:n]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _ln_bwd_kernel(dy_ref, ds_ref, s_ref, w_ref, b_ref, mu_ref, rs_ref,
                   dx_ref, dwp_ref, dbp_ref, *,
                   act, approx, has_residual):
    sf = s_ref[...].astype(jnp.float32)
    mu = mu_ref[:, :1]
    rstd = rs_ref[:, :1]
    xhat = (sf - mu) * rstd
    w = w_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    if act == "gelu":
        yln = xhat * w + b_ref[...].astype(jnp.float32)
        dy = dy * _gelu_grad(yln, approx)
    # per-block partial parameter grads; summed across blocks outside
    dwp_ref[0] = jnp.sum(dy * xhat, axis=0, keepdims=True)
    dbp_ref[0] = jnp.sum(dy, axis=0, keepdims=True)
    dxhat = dy * w
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = rstd * (dxhat - m1 - xhat * m2)
    if has_residual:
        # the sum is ALSO an output (next residual): its cotangent
        # joins the LN chain's
        dx = dx + ds_ref[...].astype(jnp.float32)
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _ln_bwd_impl(dy2, ds2, s2, w, b, mu, rs, act, approx, interpret):
    n, h = dy2.shape
    bn = _row_block(n)
    n_pad = ((n + bn - 1) // bn) * bn
    grid = n_pad // bn
    has_residual = ds2 is not None
    dyp = _pad_rows(dy2, n_pad)
    dsp = _pad_rows(ds2, n_pad) if has_residual else \
        jnp.zeros((1, h), dy2.dtype)
    sp = _pad_rows(s2, n_pad)
    mup = jnp.pad(mu, ((0, n_pad - n), (0, 0)))
    rsp = jnp.pad(rs, ((0, n_pad - n), (0, 0)))
    row_spec = pl.BlockSpec((bn, h), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    ds_spec = row_spec if has_residual else pl.BlockSpec(
        (1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)
    wb_spec = pl.BlockSpec((1, h), lambda i: (0, 0),
                           memory_space=pltpu.VMEM)
    # one (1, h) row of partials per grid step, as a (grid, 1, h)
    # array: Mosaic wants a block's last two dims to be (8k, 128k) or
    # the array's own, and (1, h) blocks of a (grid, h) array are
    # neither
    part_spec = pl.BlockSpec((1, 1, h), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((bn, _STAT_LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    kernel = functools.partial(_ln_bwd_kernel, act=act, approx=approx,
                               has_residual=has_residual)
    dx, dwp, dbp = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((n_pad, h), dy2.dtype),
                   jax.ShapeDtypeStruct((grid, 1, h), jnp.float32),
                   jax.ShapeDtypeStruct((grid, 1, h), jnp.float32)),
        grid=(grid,),
        in_specs=[row_spec, ds_spec, row_spec, wb_spec, wb_spec,
                  stat_spec, stat_spec],
        out_specs=(row_spec, part_spec, part_spec),
        interpret=interpret,
    )(dyp, dsp, sp, w.reshape(1, h), b.reshape(1, h), mup, rsp)
    dw = jnp.sum(dwp, axis=(0, 1)).astype(w.dtype)
    db = jnp.sum(dbp, axis=(0, 1)).astype(b.dtype)
    return dx[:n], dw, db


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

def _resolve_interpret(interpret):
    if interpret is not None:
        return bool(interpret)
    from . import interpret_mode, _on_tpu

    return interpret_mode() and not _on_tpu()


def _to2d(x):
    h = x.shape[-1]
    return x.reshape(-1, h), x.shape


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_layer_norm(x, weight, bias, eps=1e-5, activation=None,
                     approximate=True, interpret=None):
    """y = [gelu](LayerNorm(x) * weight + bias) over the last dim."""
    y, _, _, _ = _ln_fn_fwd_impl(x, weight, bias, eps, activation,
                                 approximate, interpret)
    return y


def _ln_fn_fwd_impl(x, weight, bias, eps, activation, approximate,
                    interpret):
    itp = _resolve_interpret(interpret)
    x2, shape = _to2d(x)
    y, _, mu, rs = _ln_fwd_impl(x2, None, weight, bias, eps, activation,
                                approximate, itp)
    return y.reshape(shape), x2, mu, rs


def _ln_fn_fwd(x, weight, bias, eps, activation, approximate, interpret):
    y, x2, mu, rs = _ln_fn_fwd_impl(x, weight, bias, eps, activation,
                                    approximate, interpret)
    return y, (x2, weight, bias, mu, rs, x.shape)


def _ln_fn_bwd(eps, activation, approximate, interpret, res, dy):
    x2, weight, bias, mu, rs, shape = res
    itp = _resolve_interpret(interpret)
    dy2 = dy.reshape(x2.shape)
    dx, dw, db = _ln_bwd_impl(dy2, None, x2, weight, bias, mu, rs,
                              activation, approximate, itp)
    return dx.reshape(shape).astype(dy.dtype), dw, db


fused_layer_norm.defvjp(_ln_fn_fwd, _ln_fn_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def fused_residual_layer_norm(x, residual, weight, bias, eps=1e-5,
                              activation=None, approximate=True,
                              interpret=None):
    """s = x + residual; y = [gelu](LayerNorm(s) * weight + bias).

    Returns (y, s) — the fused_bias_dropout_residual_layer_norm shape
    of epilogue: one pass produces both the normalized activation and
    the carried residual sum."""
    (y, s), _ = _ln_res_fwd(x, residual, weight, bias, eps, activation,
                            approximate, interpret)
    return y, s


def _ln_res_fwd(x, residual, weight, bias, eps, activation, approximate,
                interpret):
    itp = _resolve_interpret(interpret)
    x2, shape = _to2d(x)
    r2, _ = _to2d(residual)
    y, s, mu, rs = _ln_fwd_impl(x2, r2, weight, bias, eps, activation,
                                approximate, itp)
    return ((y.reshape(shape), s.reshape(shape)),
            (s, weight, bias, mu, rs, shape))


def _ln_res_bwd(eps, activation, approximate, interpret, res, cts):
    dy, ds = cts
    s2, weight, bias, mu, rs, shape = res
    itp = _resolve_interpret(interpret)
    dy2 = dy.reshape(s2.shape)
    ds2 = ds.reshape(s2.shape)
    dx, dw, db = _ln_bwd_impl(dy2, ds2, s2, weight, bias, mu, rs,
                              activation, approximate, itp)
    dx = dx.reshape(shape).astype(dy.dtype)
    # d/dx (x + residual) is identity into both inputs
    return dx, dx, dw, db


fused_residual_layer_norm.defvjp(_ln_res_fwd, _ln_res_bwd)
