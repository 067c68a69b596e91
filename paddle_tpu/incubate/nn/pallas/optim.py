"""Fused multi-tensor optimizer update Pallas kernels.

Reference capability: the multi_tensor / fused optimizer ops
(paddle/fluid/operators/fused/fused_adam_op.cu, merged_momentum_op) —
ONE kernel launch updates every parameter instead of a per-parameter
tree of small fusions.

Design: every parameter's fp32 update base (the master weight under
multi_precision, else the parameter itself), its gradient and its
moment slots are flattened, zero-padded to a chunk multiple and
stacked into ONE (chunks, rows, 128) buffer per role. The kernel grid
walks chunks; per-PARAMETER scalars (Adam bias-correction
denominators, AdamW's per-param decay mask) ride as one (1, 128) lane
row per chunk so parameters with different restored beta-pow state or
an `apply_decay_param_fun` filter still fuse. (Mosaic refuses the
(1, 1) SMEM block of a (chunks, 1) array they were first written as:
"the last two dimensions of your block shape [must be] divisible by 8
and 128 respectively, or be equal to the respective dimensions of the
overall array".) The learning rate is a traced (1, 1) SMEM scalar —
backoff/growth/schedules never recompile.

Zero padding is update-invariant for every supported rule (0 params,
0 grads, 0 moments stay 0), and unpacking slices the pads away.

`apply_fused(opt, params, grads, state, lr)` is the entry
`Optimizer.apply_gradients` calls under PADDLE_PALLAS_FUSION=1; it
returns None for anything it cannot fuse exactly (unknown rule) and
the caller falls back to the per-parameter loop.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["apply_fused", "fused_adam_chunks", "fused_sgd_chunks",
           "fused_momentum_chunks", "CHUNK_ROWS", "CHUNK_LANES"]

CHUNK_ROWS = 256
CHUNK_LANES = 128
_CHUNK = CHUNK_ROWS * CHUNK_LANES  # 32768 elements / 128 KB f32


# ---------------------------------------------------------------------------
# kernels (one grid step == one chunk)
# ---------------------------------------------------------------------------

def _adam_kernel(lr_ref, d1_ref, d2_ref, wd_ref, p_ref, g_ref, m_ref,
                 v_ref, po_ref, mo_ref, vo_ref, *, b1, b2, eps, wdc):
    lr = lr_ref[0, 0]
    d1 = d1_ref[0]             # [1, 128]: 1 - beta1^t (this step's
    d2 = d2_ref[0]             # denominator), one value on every lane
    p = p_ref[0]               # [R, 128]
    g = g_ref[0]
    if wdc:
        g = g + wdc * p        # coupled L2 (non-decoupled optimizers)
    wd = wd_ref[0]             # decoupled per-param coeff (AdamW)
    p = p * (1.0 - lr * wd)
    m = b1 * m_ref[0] + (1.0 - b1) * g
    v = b2 * v_ref[0] + (1.0 - b2) * g * g
    # divide (not multiply-by-reciprocal): bit-identical to the
    # per-parameter Adam._update rule
    mhat = m / d1
    vhat = v / d2
    po_ref[0] = p - lr * mhat / (jnp.sqrt(vhat) + eps)
    mo_ref[0] = m
    vo_ref[0] = v


def _sgd_kernel(lr_ref, p_ref, g_ref, po_ref, *, wdc):
    lr = lr_ref[0, 0]
    p = p_ref[...]
    g = g_ref[...]
    if wdc:
        g = g + wdc * p
    po_ref[...] = p - lr * g


def _momentum_kernel(lr_ref, p_ref, g_ref, v_ref, po_ref, vo_ref, *,
                     mu, nesterov, wdc):
    lr = lr_ref[0, 0]
    p = p_ref[...]
    g = g_ref[...]
    if wdc:
        g = g + wdc * p
    v = v_ref[...] * mu + g
    step = g + mu * v if nesterov else v
    po_ref[...] = p - lr * step
    vo_ref[...] = v


def _scalar_spec():
    return pl.BlockSpec((1, 1), lambda i: (0, 0),
                        memory_space=pltpu.SMEM)


def _chunk_scalar_spec():
    return pl.BlockSpec((1, 1, CHUNK_LANES), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)


def _chunk_spec():
    return pl.BlockSpec((1, CHUNK_ROWS, CHUNK_LANES),
                        lambda i: (i, 0, 0), memory_space=pltpu.VMEM)


def _audit_aliases(aliases, ins, out_shape, where):
    """PTA042 audit of a packer's hand-built input_output_aliases
    against the actual operands/results — opt-in (PADDLE_ANALYSIS=1
    or PADDLE_SANITIZE=donation), so a future edit to the pack math
    fails as a named finding instead of an XLA layout error."""
    from ....analysis import enabled as _analysis_enabled
    from ....monitor import sanitize as _sanitize

    if not (_analysis_enabled() or _sanitize._donation):
        return
    from ....analysis.donation import audit_aliases

    outs = (out_shape if isinstance(out_shape, (tuple, list))
            and not hasattr(out_shape, "shape") else (out_shape,))
    report = audit_aliases(
        aliases,
        [tuple(a.shape) for a in ins],
        [tuple(o.shape) for o in outs],
        in_dtypes=[str(a.dtype) for a in ins],
        out_dtypes=[str(o.dtype) for o in outs],
        where=where)
    if report.findings:
        import sys

        for f in report.sorted():
            print(f"[paddle_tpu.analysis] {f.format()}",
                  file=sys.stderr)
        report.record()
        if _sanitize._donation:
            raise ValueError(
                f"PTA042 input_output_aliases audit failed in "
                f"{where}:\n"
                + "\n".join(f.format() for f in report.findings))


def fused_adam_chunks(p, g, m, v, lr, d1, d2, wd, *, beta1, beta2, eps,
                      wd_coupled=0.0, interpret=False):
    """One launch of the fused Adam/AdamW rule over (G, R, 128) chunk
    buffers; d1/d2/wd are (G, 1, 128) per-chunk scalars, one value
    on every lane. Returns (new_p, new_m, new_v)."""
    G = p.shape[0]
    # ONE aliases/operands/out_shape triple shared by the audit and
    # the launch — the audit must check exactly what XLA gets
    aliases = {4: 0, 6: 1, 7: 2}
    operands = (lr.reshape(1, 1), d1, d2, wd, p, g, m, v)
    out_shape = (jax.ShapeDtypeStruct(p.shape, p.dtype),) * 3
    _audit_aliases(aliases, operands, out_shape, "fused_adam_chunks")
    kernel = functools.partial(_adam_kernel, b1=beta1, b2=beta2,
                               eps=eps, wdc=wd_coupled)
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(G,),
        in_specs=[_scalar_spec(), _chunk_scalar_spec(),
                  _chunk_scalar_spec(), _chunk_scalar_spec(),
                  _chunk_spec(), _chunk_spec(), _chunk_spec(),
                  _chunk_spec()],
        out_specs=(_chunk_spec(),) * 3,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)


def fused_sgd_chunks(p, g, lr, *, wd_coupled=0.0, interpret=False):
    G = p.shape[0]
    aliases = {1: 0}
    operands = (lr.reshape(1, 1), p, g)
    out_shape = jax.ShapeDtypeStruct(p.shape, p.dtype)
    _audit_aliases(aliases, operands, out_shape, "fused_sgd_chunks")
    kernel = functools.partial(_sgd_kernel, wdc=wd_coupled)
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(G,),
        in_specs=[_scalar_spec(), _chunk_spec(), _chunk_spec()],
        out_specs=_chunk_spec(),
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)


def fused_momentum_chunks(p, g, v, lr, *, momentum, nesterov=False,
                          wd_coupled=0.0, interpret=False):
    G = p.shape[0]
    aliases = {1: 0, 3: 1}
    operands = (lr.reshape(1, 1), p, g, v)
    out_shape = (jax.ShapeDtypeStruct(p.shape, p.dtype),) * 2
    _audit_aliases(aliases, operands, out_shape,
                   "fused_momentum_chunks")
    kernel = functools.partial(_momentum_kernel, mu=momentum,
                               nesterov=nesterov, wdc=wd_coupled)
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(G,),
        in_specs=[_scalar_spec(), _chunk_spec(), _chunk_spec(),
                  _chunk_spec()],
        out_specs=(_chunk_spec(),) * 2,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

def _segments(names, params):
    """(name, n_elems, n_chunks) per fused param, in a stable order.
    Zero-size params keep ne=0 (their whole chunk is padding) — the
    pad math below must see the TRUE element count or the stacked
    buffer stops being a chunk multiple."""
    segs = []
    for n in names:
        ne = int(np.prod(np.shape(params[n])))
        segs.append((n, ne, max(1, -(-ne // _CHUNK))))
    return segs


def _pack(segs, arrays):
    """arrays: name -> array (any shape/dtype). Returns the stacked
    f32 (G, R, 128) buffer, zero-padded per segment."""
    flats = []
    for n, ne, nc in segs:
        a = jnp.ravel(arrays[n]).astype(jnp.float32)
        pad = nc * _CHUNK - ne
        if pad:
            a = jnp.pad(a, (0, pad))
        flats.append(a)
    return jnp.concatenate(flats).reshape(-1, CHUNK_ROWS, CHUNK_LANES)


def _pack_scalars(segs, values):
    """Per-param traced/plain scalars -> (G, 1, 128) f32: each
    chunk's value repeated across one lane row."""
    parts = [jnp.full((nc, 1, CHUNK_LANES),
                      jnp.asarray(values[n], jnp.float32))
             for n, ne, nc in segs]
    return jnp.concatenate(parts)


def _unpack(segs, buf, shapes):
    out = {}
    flat = buf.reshape(-1)
    off = 0
    for n, ne, nc in segs:
        out[n] = flat[off:off + ne].reshape(shapes[n])
        off += nc * _CHUNK
    return out


# ---------------------------------------------------------------------------
# Optimizer.apply_gradients entry
# ---------------------------------------------------------------------------

def apply_fused(opt, params, grads, state, lr):
    """Fused replacement for the per-parameter apply_gradients loop.
    `grads` is already clipped. Returns (new_params, new_state), or
    None when this optimizer/state shape can't fuse exactly."""
    kind = getattr(opt, "_pallas_fused_kind", None)
    if kind not in ("sgd", "momentum", "adam", "adamw"):
        return None
    from . import interpret_mode, _on_tpu

    interpret = interpret_mode() and not _on_tpu()
    names = [n for n in params if grads.get(n) is not None]
    passthrough = [n for n in params if grads.get(n) is None]
    if not names:
        return dict(params), {n: state[n] for n in state}
    wd = opt._wd_coeff()
    decoupled = bool(getattr(opt, "_decoupled_wd", False))
    wd_coupled = 0.0 if decoupled else float(wd)
    segs = _segments(names, params)
    shapes = {n: np.shape(params[n]) for n in names}
    # update base: fp32 master weight when present (multi_precision),
    # else the parameter itself (computed in f32, cast back)
    masters = {n: state[n].get("master_weight") for n in names}
    base = {n: (masters[n] if masters[n] is not None else params[n])
            for n in names}
    pbuf = _pack(segs, base)
    gbuf = _pack(segs, grads)
    lr32 = jnp.asarray(lr, jnp.float32)

    new_state = {n: dict(state[n]) for n in state}
    if kind in ("adam", "adamw"):
        mbuf = _pack(segs, {n: state[n]["moment1"] for n in names})
        vbuf = _pack(segs, {n: state[n]["moment2"] for n in names})
        d1s, d2s, wds = {}, {}, {}
        fun = getattr(opt, "_apply_decay_param_fun", None)
        for n in names:
            b1p = state[n]["beta1_pow"] * opt._beta1
            b2p = state[n]["beta2_pow"] * opt._beta2
            new_state[n]["beta1_pow"] = b1p
            new_state[n]["beta2_pow"] = b2p
            d1s[n] = 1.0 - b1p
            d2s[n] = 1.0 - b2p
            apply_decay = decoupled and (fun is None or fun(n))
            wds[n] = float(wd) if apply_decay else 0.0
        npbuf, nmbuf, nvbuf = fused_adam_chunks(
            pbuf, gbuf, mbuf, vbuf, lr32,
            _pack_scalars(segs, d1s), _pack_scalars(segs, d2s),
            _pack_scalars(segs, wds), beta1=opt._beta1,
            beta2=opt._beta2, eps=opt._epsilon,
            wd_coupled=wd_coupled, interpret=interpret)
        for n, m in _unpack(segs, nmbuf, shapes).items():
            new_state[n]["moment1"] = m
        for n, v in _unpack(segs, nvbuf, shapes).items():
            new_state[n]["moment2"] = v
    elif kind == "momentum":
        vbuf = _pack(segs, {n: state[n]["velocity"] for n in names})
        npbuf, nvbuf = fused_momentum_chunks(
            pbuf, gbuf, vbuf, lr32, momentum=opt._momentum,
            nesterov=opt._use_nesterov, wd_coupled=wd_coupled,
            interpret=interpret)
        for n, v in _unpack(segs, nvbuf, shapes).items():
            new_state[n]["velocity"] = v
    else:  # sgd
        npbuf = fused_sgd_chunks(pbuf, gbuf, lr32,
                                 wd_coupled=wd_coupled,
                                 interpret=interpret)

    new_base = _unpack(segs, npbuf, shapes)
    new_params = {}
    for n in names:
        if masters[n] is not None:
            new_state[n]["master_weight"] = new_base[n]
            new_params[n] = new_base[n].astype(params[n].dtype)
        else:
            new_params[n] = new_base[n].astype(params[n].dtype)
    for n in passthrough:
        new_params[n] = params[n]
    return new_params, new_state
