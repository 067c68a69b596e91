"""Dropless expert FFN: scores, bias-corrected top-k, grouped matmuls
over the experts held HERE.

The GShard layer next door (`MoELayer`) gives every expert a static
capacity and drops what does not fit. This one drops nothing: the
(token, expert) assignments are sorted by expert, the rows of one
expert lie together, and a grouped matmul multiplies each group by
its own expert's matrices — M rows in, M rows out, whatever the
routing's skew. The shapes stay static (M = tokens x top_k); only the
group sizes are data.

Two implementations multiply the groups, one a program, chosen while
it is traced from platform, mesh and static shape
(`expert_kernel_supported`; no switch). On a TPU, outside a
multi-device mesh, for bf16 matrices and as many rows as fit in VMEM
beside a block of weights (every decode program of the three served
expert models, GLM's and LFM2's prefill buckets): the repo's Pallas
kernel (`incubate/nn/pallas/grouped_matmul.py`), whose row tile
follows the rows a group holds and which reads each hit expert once,
at the HBM's speed. Everywhere else (the CPU, a live mesh, float32,
LongCat's prefill buckets of 24 576 rows): `jax.lax.ragged_dot`,
which on a TPU multiplies every group as a 512-row tile (PERF.md,
PR 35).
Neither has a derivative here (R1): the kernel raises if asked.

Routing is DeepSeek-V3's `noaux_tc` with one group (what
`glm4_moe_lite` publishes): scores `s = sigmoid(u W_r)` in float32,
the `top_k` largest of `s + b` chosen (`b`, the
`e_score_correction_bias`, is a buffer that steers the CHOICE and
never enters the weights), weights `scale * s_i / (sum of the chosen
s + 1e-20)`.

Stacked layers: the experts of a layer scan are stored `[L, E, ...]`.
Slicing layer `l` out for the grouped matmul makes the TPU compiler
copy all of its experts (0.75 GiB a layer at 64 x 2048 x 3072 bf16:
compiled for the v5e, PR 27), so the stack is instead VIEWED as
`[L*E, ...]` groups (a reshape of leading dimensions) and the group
sizes of every other layer are zero — the block tables' `l * N`
shift of `serving.model_runner`, for weights.

A share of a layer (expert parallelism's cut, one rank of it): the
router keeps its published width, the ids range over every expert of
the layer, and this chip holds experts `first .. first + E` of them
(`dropless_expert_ffn(first=)`). An assignment to an expert held
elsewhere sorts behind every held group, belongs to no group and
adds nothing: the result is this chip's PART of the layer's sum, and
nothing stands in for the other chips or for the exchange with them.
LongCat-Flash's router also has outputs beyond the real experts,
`zero-compute` experts of type identity, whose SwiGLU is the token
itself: `identity_expert_sum`, no matmul. Its rule
(`softmax_topk_route`): `s = softmax(u W_r)` over real and zero
experts alike, the `top_k` largest of `s + b` chosen, weights `scale
* s_i`, NOT renormalised. Mellum's is the third combination (softmax
scores, no bias, renormalised): all three are `topk_route(..., score,
renormalise)`, ONE function of the score function and of whether the
chosen scores are renormalised (PR 38), the two names above its
cases, in the same order of operations as before.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["topk_route", "sigmoid_topk_route", "softmax_topk_route",
           "dropless_expert_ffn", "identity_expert_sum", "expert_counts",
           "expert_kernel_supported"]


def topk_route(u, router_w, bias, top_k, scale, score, renormalise):
    """u [T, H] -> (expert ids [T, k] int32 over ALL the router's
    outputs, weights [T, k] float32): the ONE routing rule, of which
    the served models publish three cases. Scores `s = score(u W_r)`
    (`jax.nn.sigmoid`, or `jax.nn.softmax` over the outputs) in
    float32; the matmul runs in true float32 (`HIGHEST`: the TPU's
    default is one bf16 pass, which flips near-ties between the k-th
    and the next expert); the `top_k` largest of `s + bias` chosen
    (`bias` None: of `s`); weights `scale * s_i`, over the sum of the
    chosen (+ 1e-20) where `renormalise`."""
    scores = score(jnp.dot(
        u.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(
        scores if bias is None else scores + bias.astype(jnp.float32),
        top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = scale * chosen
    if renormalise:
        weights = weights / (chosen.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), weights


def sigmoid_topk_route(u, router_w, bias, top_k, scale):
    """GLM-4.7-Flash's and LFM2's case: sigmoid scores, chosen by
    `s + bias`, renormalised."""
    return topk_route(u, router_w, bias, top_k, scale, jax.nn.sigmoid,
                      True)


def softmax_topk_route(u, router_w, bias, top_k, scale):
    """LongCat-Flash's case: softmax scores over real and zero
    experts alike, chosen by `s + bias`, NOT renormalised."""
    return topk_route(u, router_w, bias, top_k, scale,
                      functools.partial(jax.nn.softmax, axis=-1), False)


def expert_counts(idx, n_experts, live=None):
    """Tokens per expert [E] int32 from the chosen ids [T, k] (an id
    outside `0 .. E` counts nowhere: a share passes `idx - first`);
    rows where `live` [T] is False (padding, inactive batch slots)
    are left out."""
    hot = jax.nn.one_hot(idx, n_experts, dtype=jnp.int32)
    if live is not None:
        hot = hot * live.astype(jnp.int32)[:, None, None]
    return hot.sum((0, 1))


def identity_expert_sum(u, idx, weights, first_zero):
    """What a token's picks of zero-compute experts of type identity
    (ids >= `first_zero`) add: `(sum of their weights) * u[t]`."""
    w = jnp.where(idx >= first_zero, weights, 0.0).sum(-1, keepdims=True)
    return (w * u.astype(jnp.float32)).astype(u.dtype)


def expert_kernel_supported(tokens, top_k, n_experts, hidden, width,
                            dtype):
    """Do the two grouped matmuls of an expert layer of this static
    shape (`tokens * top_k` rows over `n_experts` groups, `[hidden,
    2 * width]` then `[width, hidden]` matrices of `dtype`) run in
    the Pallas kernel here? `grouped_matmul_supported`'s answer for
    both (platform, mesh and shape; no switch). The ONE predicate:
    `dropless_expert_ffn` asks it while a program is traced, a
    serving runner when the engine counts that program's dispatch
    (`serve/moe/layer_steps_kernel`)."""
    from ....nn.pallas import grouped_matmul as gm

    rows = tokens * top_k
    return gm.grouped_matmul_supported(
        rows, n_experts, hidden, 2 * width, dtype) \
        and gm.grouped_matmul_supported(rows, n_experts, width, hidden,
                                        dtype)


def _grouped_matmul(t, k, w13, w2, dtype):
    """The Pallas grouped matmul where it runs (`expert_kernel_
    supported`), else None: `jax.lax.ragged_dot`."""
    if w13.dtype != dtype or w2.dtype != dtype \
            or not expert_kernel_supported(
                t, k, w13.shape[-3], w13.shape[-2], w2.shape[-2], dtype):
        return None
    from ....nn import pallas as _pl

    return functools.partial(_pl.grouped_matmul.grouped_matmul,
                             interpret=_pl.interpret_mode())


def dropless_expert_ffn(u, idx, weights, w13, w2, layer=None, first=None):
    """sum_i weights[t, i] * SwiGLU_{idx[t, i]}(u[t]) for every token,
    over the experts held here.

    u [T, H]; idx/weights [T, k]; `w13` holds each expert's gate and
    up projections side by side, `[E, H, 2F]`, `w2` its down
    projection `[E, F, H]`. With `layer` (a traced index) the two are
    stacks `[L, E, H, 2F]` / `[L, E, F, H]` and layer `layer`'s
    experts are used, without slicing them out (module docstring).
    No token is dropped: an expert that takes every token gets a
    group of T rows. `first=None`: the ids are the E held experts'
    own numbers. With `first` the ids range over a wider layer of
    which this chip holds experts `first .. first + E`; a pick
    outside them is computed elsewhere (or is a zero-compute
    expert's) and adds nothing to this partial sum."""
    t, k = idx.shape
    n_experts = w13.shape[-3]
    flat = idx.reshape(t * k)
    if first is not None:
        # absent picks get group number E: sorted behind every held
        # group, in no group's size (a scatter drops an index out of
        # bounds), weight 0
        held = (idx >= first) & (idx < first + n_experts)
        flat = jnp.where(held, idx - first, n_experts).reshape(t * k)
        weights = jnp.where(held, weights, 0.0)
    order = jnp.argsort(flat, stable=True)
    rows = jnp.take(u, order // k, axis=0)              # [T*k, H]
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    grouped = _grouped_matmul(t, k, w13, w2, rows.dtype)
    if layer is not None:
        groups = w13.shape[0] * n_experts
        if grouped is None:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((groups,), jnp.int32), sizes,
                (layer * n_experts,))
        w13 = w13.reshape((groups,) + w13.shape[2:])
        w2 = w2.reshape((groups,) + w2.shape[2:])
    if grouped is None:
        grouped = jax.lax.ragged_dot
    else:
        grouped = functools.partial(
            grouped, first_group=0 if layer is None
            else layer * n_experts)
    gate, up = jnp.split(grouped(rows, w13, sizes), 2, axis=-1)
    out = grouped(jax.nn.silu(gate) * up, w2, sizes)
    # back to token order: row j of the sorted list is assignment
    # order[j]; its inverse gathers instead of scattering
    out = jnp.take(out, jnp.argsort(order), axis=0).reshape(t, k, -1)
    if first is not None:
        # rows behind the last group are whatever the grouped matmul
        # leaves there: 0 x that must be 0
        out = jnp.where(held[..., None], out, 0)
    return jnp.einsum("tkh,tk->th", out.astype(jnp.float32),
                      weights).astype(u.dtype)
