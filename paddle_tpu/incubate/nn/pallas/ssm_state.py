"""The decode step's state-space update for the whole batch in one
pass over the state: `ssm.ssm_step` for every slot, reading each
slot's float32 state `[H, N, P]` once and writing it once, in place.

The state lives in the serving cache as ONE stacked array `[L,
max_batch, H, N, P]` (a layer a row of its first axis, a slot a row of
its second). The kernel takes the whole array and a static layer
index, and its grid walks that layer's rows by offset: the stack is
never sliced and never restacked (the pools' rule), and the output
aliases the input. A grid step is one slot and one group of heads (the
`H / G` heads that read one group's B and C): their state block,
`H / G x N x P` float32 (2 MiB at Falcon-H1's 16 x 256 x 128), the
group's B and C rows, the heads' decays and `dt x` rows. Inactive
slots ride along; the next prefill into a slot overwrites its state
whole.

In the kernel a head's update is `S * decay + B (x) (dt x)` with B a
column and `dt x` a row, and `y = sum over N of C * S` a reduction
over sublanes: no matrix unit, exact float32, the order of operations
`ssm_step`'s. Chosen with no switch: `ssm_state_supported` answers
from platform, mesh and shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_state_update", "ssm_state_supported"]

# a grid step's state block, at most: in and out, double-buffered, four
# of them beside a few rows stay inside the default scoped VMEM
_BLOCK_BYTES = 2 * 2 ** 20


def ssm_state_supported(heads, groups, d_state, head_dim, dtype):
    """Does the decode update of a `[H, N, P]` state of `dtype` run in
    the Pallas kernel here? On a TPU outside a multi-device mesh (no
    shard_map island), a float32 state whose head rows fill whole
    lanes and whose group blocks fill whole sublane groups and fit the
    block budget; on the CPU only in the interpreter
    (PADDLE_PALLAS_INTERPRET=1), any shape."""
    from . import _on_tpu, _partitioned, interpret_mode

    if heads % groups:
        return False
    if not _on_tpu():
        return interpret_mode()
    per_group = heads // groups
    return (not _partitioned() and jnp.dtype(dtype) == jnp.float32
            and head_dim % 128 == 0 and d_state % 8 == 0
            and per_group % 8 == 0
            and per_group * d_state * head_dim * 4 <= _BLOCK_BYTES)


def _kernel(decay_ref, xdt_ref, b_ref, c_ref, s_ref, y_ref, out_ref, *,
            per_group, d_state):
    # B and C of this group as columns [N, 1] (a row broadcast to a
    # whole sublane group, transposed)
    b_col = jnp.transpose(jnp.broadcast_to(b_ref[0], (8, d_state)))[:, :1]
    c_col = jnp.transpose(jnp.broadcast_to(c_ref[0], (8, d_state)))[:, :1]
    decay = decay_ref[0]                                  # [1, heads]
    for h in range(per_group):
        s = s_ref[h] * decay[:, h:h + 1] + b_col * xdt_ref[h:h + 1, :]
        out_ref[h] = s
        y_ref[h:h + 1, :] = jnp.sum(s * c_col, axis=0, keepdims=True)


def ssm_state_update(state, layer, x, dt, A, B, C, interpret=False):
    """state [L, max_batch, H, N, P] float32, `layer` (a Python int)
    the row of its first axis to update; x [max_batch, H, P], dt
    [max_batch, H], A [H], B / C [max_batch, G, N]. Returns (y
    [max_batch, H, P] float32, state), the state updated in place
    where the caller donates it."""
    f32 = jnp.float32
    _, bsz, heads, d_state, head_dim = state.shape
    groups = B.shape[-2]
    per = heads // groups
    dt = dt.astype(f32)
    # [slot x group, 1, heads of the group]: a row a grid step
    decay = jnp.exp(dt * A.astype(f32)).reshape(bsz * groups, 1, per)
    xdt = dt[..., None] * x.astype(f32)
    rows = (bsz * groups, 1, d_state)
    b, c = B.astype(f32).reshape(rows), C.astype(f32).reshape(rows)

    def by_group(s, g):
        return (s * groups + g, 0, 0)

    state_block = pl.BlockSpec((None, None, per, d_state, head_dim),
                               lambda s, g: (layer, s, g, 0, 0))
    head_rows = pl.BlockSpec((None, per, head_dim), lambda s, g: (s, g, 0))
    return pl.pallas_call(
        functools.partial(_kernel, per_group=per, d_state=d_state),
        grid=(bsz, groups),
        in_specs=[pl.BlockSpec((1, 1, per), by_group), head_rows,
                  pl.BlockSpec((1, 1, d_state), by_group),
                  pl.BlockSpec((1, 1, d_state), by_group), state_block],
        out_specs=[head_rows, state_block],
        out_shape=[jax.ShapeDtypeStruct((bsz, heads, head_dim), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="ssm_state",
    )(decay, xdt, b, c, state)
