"""The Mamba-2 state-space recurrence (state-space duality, SSD) in
plain `jax.numpy`: the chunked scan a prefill runs over a prompt, and
the one-token update a decode step runs (the CPU's path, and the
reference `pallas/ssm_state.py` is tested against).

Heads `H` of `P` values read groups `G` of `N` state dimensions (head
`h` reads group `h // (H / G)`); per position `t`, with `dt_t > 0` a
head and `A < 0` a head:

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t (x) x_t        S: [H, N, P]
    y_t = C_t . S_t                                      y: [H, P]

The state is stored `[H, N, P]`, a head's `P` values on the lanes: the
published `[H, P, N]` transposed, so that a decode step's update and
its read of `y` need no transposed tile. Everything here is float32:
the state is a running sum over thousands of steps with decays near
1. The skip term `D x_t` is the model's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssd_chunked", "ssm_step"]

_HI = jax.lax.Precision.HIGHEST


def _by_head(bc, heads):
    """B or C `[..., G, N]` repeated to `[..., H, N]`: head h reads
    group h // (H / G)."""
    return jnp.repeat(bc, heads // bc.shape[-2], axis=-2)


def ssm_step(state, x, dt, A, B, C):
    """One position of the recurrence for a batch of sequences:
    state [..., H, N, P], x [..., H, P], dt [..., H], A [H], B / C
    [..., G, N]. Returns (y [..., H, P], the new state), float32."""
    f32 = jnp.float32
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    heads = x.shape[-2]
    decay = jnp.exp(dt * A.astype(f32))
    state = state * decay[..., None, None] \
        + _by_head(B, heads)[..., :, None] * (dt[..., None] * x)[..., None, :]
    y = jnp.sum(state * _by_head(C, heads)[..., :, None], axis=-2)
    return y, state


def ssd_chunked(x, dt, A, B, C, chunk=128, length=None):
    """The recurrence over one sequence from a zero state, in chunks of
    `chunk` positions: inside a chunk the matmul form (each position's
    `y` from every earlier position of the chunk through the decays
    between them), across chunks a scan over the chunks' states.
    x [T, H, P], dt [T, H], A [H], B / C [T, G, N]. Positions at or
    after `length` (a traced scalar; None: T) get dt = 0: they neither
    decay nor add to the state, so that the state returned is the one
    after position `length - 1` and a prompt's padded tail leaves it
    as the prompt's real end left it (y before them is causal and
    unchanged). Returns (y [T, H, P], state [H, N, P]), float32."""
    f32 = jnp.float32
    t, heads, p = x.shape
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    if length is not None:
        dt = jnp.where((jnp.arange(t) < length)[:, None], dt, 0.0)
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in (x, dt, B, C))
    n_c = (t + pad) // chunk
    x, dt = x.reshape(n_c, chunk, heads, p), dt.reshape(n_c, chunk, heads)
    Bh = _by_head(B, heads).reshape(n_c, chunk, heads, -1)
    Ch = _by_head(C, heads).reshape(n_c, chunk, heads, -1)
    a = dt * A.astype(f32)                                  # [c, L, H]
    cs = jnp.cumsum(a, axis=1)
    # seg[c, t, s] = sum_{s < k <= t} a_k, summed as such (not as a
    # difference of two cumulative sums, which loses the small decays of
    # a long chunk to cancellation); -inf above the diagonal
    k_after_s = jnp.tril(jnp.ones((chunk, chunk), bool), -1)[None, :, :, None]
    seg = jnp.cumsum(jnp.where(k_after_s, a[:, :, None], 0.0), axis=1)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))       # [c, t, s, H]
    # inside a chunk: y_t = sum_{s <= t} e^{seg_ts} (C_t . B_s) dt_s x_s
    cb = jnp.einsum("cthn,cshn->ctsh", Ch, Bh, precision=_HI)
    w = cb * decay * dt[:, None]
    y = jnp.einsum("ctsh,cshp->cthp", w, x, precision=_HI)
    # each chunk's own contribution to the state at its end
    into_end = decay[:, -1] * dt                            # [c, L, H]
    own = jnp.einsum("clh,clhn,clhp->chnp", into_end, Bh, x, precision=_HI)

    def carry(state, xs):
        own_c, decay_c = xs
        return state * decay_c[:, None, None] + own_c, state

    final, entering = jax.lax.scan(
        carry, jnp.zeros((heads,) + own.shape[2:], f32),
        (own, jnp.exp(cs[:, -1])))
    # the state a chunk starts from, decayed to each of its positions
    y = y + jnp.einsum("clhn,chnp->clhp", Ch * jnp.exp(cs)[..., None],
                       entering, precision=_HI)
    return y.reshape(n_c * chunk, heads, p)[:t], final
