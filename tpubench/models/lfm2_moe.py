"""LFM2-24B-A2B (`model_type` `lfm2_moe`; LiquidAI/LFM2-24B-A2B config.json):
how the benchmark builds the engine from a configuration file, what a decode
step cannot do without (from shapes and the program's routing counters, never
from what an implementation happens to read), and the plain float32 reference.

The block (`N` RMSNorm with a weight and no bias, eps `norm_eps`; no
projection has a bias). Layer `i` of `layer_types`:

    h  = x + Op_i(N_op(x))            x' = h + FFN_i(N_ffn(h))
    logits = N_emb(x_last) . E^T      (the head tied to the embedding)

- `conv`, the gated short convolution (`conv_L_cache` L = 3): `[B | C | X] =
  u W_in`, `z_t = B_t * X_t`, `c_t = sum_{j<L} w[j] * z_{t-(L-1)+j}`
  (depthwise, causal, `z` zero before position 0), `y_t = (C_t * c_t) W_out`.
- `full_attention`: `num_attention_heads` query heads over
  `num_key_value_heads` K/V heads of `hidden_size / num_attention_heads`;
  `q` and `k` RMS-normalised per head (own gains) BEFORE rotary; rotary over
  the whole head, theta `rope_theta`, half-split pairing; scores `/
  sqrt(head)`, causal, softmax; query head `h` reads K/V head `h // G`; `W_o`.
- FFN: dense SwiGLU of `intermediate_size` for `i < num_dense_layers`, else
  `s = sigmoid(u W_r)` in float32, the `num_experts_per_tok` largest of `s +
  b` chosen, weights `routed_scaling_factor x s_i / (sum of the chosen s +
  1e-6)`, each expert a SwiGLU of `moe_intermediate_size`; no shared expert.

The reference computes exactly that: whole-sequence convolution by shifting
(no state), K/V heads repeated, every expert for every token with the
router's weight (0 where not chosen), no cache, kernel or batching.
Departures from the published description are in the configuration file
(`reduced`, `assumed`): depth, the head's size, the split order, the rotary
pairing, the taps' scale, a serving `max_seq_len`. The program stores the taps
`[L, H]` and the gate and up projections side by side (`w13`); the reference
reads the program's tree.
"""
from __future__ import annotations

import functools
import math
import statistics

PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads", "conv_L_cache",
             "conv_bias", "num_dense_layers", "num_experts",
             "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
             "routed_scaling_factor", "max_position_embeddings", "norm_eps")

# what build_engine last built from (the model configuration and the file's
# `serve` group): the kinds hand teacher_forced_deficits the parameter tree
# and `n_head` only
_BUILT = {}


# -- counts from shapes ------------------------------------------------------

def layer_types(config):
    """The layers held: the first `num_hidden_layers` of the published
    list, which the file keeps whole."""
    return tuple(config["layer_types"][:config["num_hidden_layers"]])


def _widths(config):
    c = config
    h, heads = c["hidden_size"], c["num_attention_heads"]
    d = h // heads
    kv = c["num_key_value_heads"] * d
    types = layer_types(c)
    n_dense = c["num_dense_layers"]
    return {
        "conv": 3 * h * h + c["conv_L_cache"] * h + h * h,
        "attn": h * (heads * d + 2 * kv) + heads * d * h + 2 * d,
        "norms": 2 * h,
        "dense_ffn": 3 * h * c["intermediate_size"],
        "expert": 3 * h * c["moe_intermediate_size"],
        "router": h * c["num_experts"] + c["num_experts"],
        "embed": c["vocab_size"] * h,
        "n_conv": sum(t == "conv" for t in types),
        "n_attn": sum(t == "full_attention" for t in types),
        "n_dense": n_dense, "n_moe": len(types) - n_dense,
        "kv_row": kv, "tied": bool(c.get("tie_word_embeddings", True)),
    }


def _outside_experts(w, h):
    """Parameters outside the routed experts and the routers: operators,
    norms, dense FFNs, the embedding (and an untied head)."""
    return (w["n_conv"] * w["conv"] + w["n_attn"] * w["attn"]
            + (w["n_conv"] + w["n_attn"]) * w["norms"]
            + w["n_dense"] * w["dense_ffn"] + h
            + w["embed"] * (1 if w["tied"] else 2))


def param_count(config):
    """5 267 090 176 at the first 10 layers (8 convolutions, 2 attentions; 2
    dense and 8 expert FFNs), every expert and the whole vocabulary held,
    the head tied."""
    w = _widths(config)
    return _outside_experts(w, config["hidden_size"]) + w["n_moe"] * (
        config["num_experts"] * w["expert"] + w["router"])


def kv_bytes_per_token(config, itemsize):
    """What a token leaves in the paged pools: a key and a value row of
    `num_key_value_heads x head` an attention."""
    w = _widths(config)
    return w["n_attn"] * 2 * w["kv_row"] * itemsize


def state_bytes_per_seq(config, itemsize):
    """What a sequence keeps beside them, whatever its length: the last
    `conv_L_cache - 1` gated inputs of every convolution."""
    return (_widths(config)["n_conv"] * (config["conv_L_cache"] - 1)
            * config["hidden_size"] * itemsize)


def decode_least(config, ctx_tokens, batch, experts_hit, itemsize):
    """(flops, bytes) one decode step cannot do without. Bytes: every weight
    outside the routed experts once (the embedding is the head), the routers
    in float32, `experts_hit` (expert, layer) pairs' three matrices, a row of
    the embedding per sequence, every live token's key and value rows once
    an attention, and every sequence's convolution states read and written.
    Flops: 2 per active parameter per sequence, plus scores and values per
    query head and live token."""
    c, w = config, _widths(config)
    h, heads = c["hidden_size"], c["num_attention_heads"]
    fixed = _outside_experts(w, h)
    nbytes = (fixed * itemsize + w["n_moe"] * w["router"] * 4
              + experts_hit * w["expert"] * itemsize
              + batch * h * itemsize
              + kv_bytes_per_token(c, itemsize) * ctx_tokens
              + 2 * batch * state_bytes_per_seq(c, itemsize))
    active = fixed + w["n_moe"] * (c["num_experts_per_tok"] * w["expert"]
                                   + w["router"])
    flops = 2 * active * batch + 2 * w["n_attn"] * heads * ctx_tokens * (
        2 * (h // heads))
    return flops, nbytes


def _window(run):
    """(median live context, (expert, layer) pairs hit a step) of the
    window, from the steps' records and the program's routing counters."""
    ctx = run.samples.get("step_ctx_tokens")
    if not ctx or "close" not in run.counters:
        return None
    layer_steps = run.counter_delta("serve/moe/layer_steps")
    if not layer_steps:
        return None
    n_moe = _widths(run.config)["n_moe"]
    return (statistics.median(ctx),
            run.counter_delta("serve/moe/experts_hit") / layer_steps * n_moe)


def _itemsize(run):
    return {"float32": 4, "bfloat16": 2}[run.config["serve"]["weight_dtype"]]


def least_decode(run, n_events):
    """(flops, bytes) of `n_events` decode programs."""
    win = _window(run)
    if win is None:
        return None
    flops, nbytes = decode_least(run.config, win[0],
                                 run.config["serve"]["max_batch"], win[1],
                                 _itemsize(run))
    return n_events * flops, n_events * nbytes


def kernels_least(config, ctx_tokens, batch, experts_hit, itemsize):
    """(flops, bytes) the custom calls of ONE decode step cannot do without:
    the paged attention launches (every live token's key and value rows
    once an attention; scores and values per query head) and the grouped
    matmuls (the hit experts' three matrices once; 2 flops per expert
    parameter per assignment)."""
    c, w = config, _widths(config)
    heads, d = c["num_attention_heads"], c["hidden_size"] // c[
        "num_attention_heads"]
    nbytes = (kv_bytes_per_token(c, itemsize) * ctx_tokens
              + experts_hit * w["expert"] * itemsize)
    flops = (2 * w["n_attn"] * heads * ctx_tokens * 2 * d
             + 2 * w["n_moe"] * batch * c["num_experts_per_tok"]
             * w["expert"])
    return flops, nbytes


def least_kernels(run, n_events):
    """(flops, bytes) of the trace's custom calls: they are the decode steps'
    (the prefills are over before the window opens), so many steps' as the
    trace holds decode programs (the most frequent `jit__unknown`, found as
    `decode_roofline.*` finds it), not `n_events` over a count of calls a
    step, which is the compiler's to change (today a step makes 26: a paged
    attention an attention, two grouped matmuls and one `ragged-dot-metadata`
    an expert layer)."""
    win = _window(run)
    tr = run.reduced_trace()
    if win is None or tr is None:
        return None
    steps = len(tr.module_events("jit__unknown", min(tr.devices), True))
    if not steps:
        return None
    flops, nbytes = kernels_least(run.config, win[0],
                                  run.config["serve"]["max_batch"], win[1],
                                  _itemsize(run))
    return steps * flops, steps * nbytes


# -- the system under test ---------------------------------------------------

def model_config(config):
    from paddle_tpu.text.models.lfm2_moe import Lfm2MoeConfig

    return Lfm2MoeConfig(
        dtype=config["serve"]["weight_dtype"],
        rope_theta=config["rope_parameters"]["rope_theta"],
        tie_word_embeddings=config["tie_word_embeddings"],
        conv_init_std=config["conv_init_std"],
        qk_norm_init=config.get("qk_norm_init", 1.0),
        layer_types=layer_types(config),
        **{k: config[k] for k in PUBLISHED})


def build_engine(config, seed):
    """LLMEngine(model.eval()) with the serving settings the file states;
    weights drawn on the device from the seed."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import LLMEngine
    from paddle_tpu.text.models.lfm2_moe import Lfm2MoeForCausalLM

    s = config["serve"]
    cfg = model_config(config)
    paddle.seed(int(seed) % 2147483647)
    model = Lfm2MoeForCausalLM(cfg)
    model.eval()
    _BUILT.update(config=cfg, serve=s)
    return LLMEngine(model, max_batch=s["max_batch"],
                     block_size=s["block_size"],
                     num_blocks=s.get("num_blocks"), dtype=s["kv_dtype"],
                     spec_k=s["spec_k"], prefix_cache=s["prefix_cache"],
                     max_seq_len=config["n_positions"],
                     run_ahead=s["run_ahead"])


# -- the plain reference -----------------------------------------------------

def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a, jnp.float32)


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _swiglu(u, w13, w2):
    """SwiGLU with the gate and up projections side by side in `w13`, each
    half cast up on its own."""
    half = w13.shape[-1] // 2
    return (_silu(u @ _f32(w13[:, :half])) * (u @ _f32(w13[:, half:]))) \
        @ _f32(w2)


def reference_logits(params, ids, cfg, start=0, n_rows=None, q_block=256):
    """The forward pass in plain float32 jax.numpy at `highest` matmul
    precision, from the equations of the module's docstring. `params` is
    the engine's own tree (text/models/lfm2_moe.py: a list of layers, each
    its own tree), cast up a matrix (for `w13` half a matrix) and an expert
    at a time, one layer after another; attention runs in blocks of
    `q_block` queries; only rows `start : start + n_rows` meet the head, so
    that 4096 positions fit beside a live engine. ids [S] -> logits
    [n_rows, V]."""
    import jax
    import jax.numpy as jnp

    eps = cfg.norm_eps
    heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
    d = cfg.hidden_size // heads
    taps = cfg.conv_L_cache
    s = ids.shape[0]
    q_block = min(q_block, s)
    if s % q_block:
        raise ValueError(f"{s} positions in query blocks of {q_block}")
    pos = jnp.arange(s, dtype=jnp.float32)

    def norm(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * _f32(w)

    def rotary(x):
        """R_t over the last dim of x [S, heads, d], pairing dim i with
        i + d/2."""
        half = d // 2
        inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = (pos[:, None] * inv)[:, None, :]               # [S, 1, half]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                                x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)

    def short_conv(u, cp):
        h = u.shape[-1]
        bcx = u @ _f32(cp["w_in"])
        gate_b, gate_c, x = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
        z = gate_b * x
        w = _f32(cp["taps"])                                  # [L, H]
        c = w[taps - 1] * z
        for back in range(1, taps):     # z_{t - back}, zero before 0
            shifted = jnp.concatenate(
                [jnp.zeros((back, h), jnp.float32), z[:s - back]], 0)
            c = c + w[taps - 1 - back] * shifted
        return (gate_c * c) @ _f32(cp["w_out"])

    def attention(u, ap):
        qkv = u @ _f32(ap["wqkv"])
        q = qkv[:, :heads * d].reshape(s, heads, d)
        k = qkv[:, heads * d:(heads + kv_heads) * d].reshape(s, kv_heads, d)
        v = qkv[:, (heads + kv_heads) * d:].reshape(s, kv_heads, d)
        q = rotary(norm(q, ap["q_norm"]))
        k = rotary(norm(k, ap["k_norm"]))
        # query head h reads K/V head h // G
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i, q_block)
            scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
            seen = (i + jnp.arange(q_block))[:, None] >= jnp.arange(s)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        out = jax.lax.map(block, jnp.arange(0, s, q_block))
        return out.reshape(s, heads * d) @ _f32(ap["wo"])

    def experts(u, mp):
        scores = jax.nn.sigmoid(u @ _f32(mp["router_w"]))     # [S, E]
        _, chosen = jax.lax.top_k(scores + _f32(mp["router_b"]),
                                  cfg.num_experts_per_tok)
        picked = jnp.take_along_axis(scores, chosen, -1)
        weight = cfg.routed_scaling_factor * picked / (
            picked.sum(-1, keepdims=True) + 1e-6)
        # w[t, e]: the weight of expert e for token t, 0 if not chosen
        w = jnp.zeros_like(scores).at[
            jnp.arange(s)[:, None], chosen].set(weight)

        def one(acc, xs):
            w13, w2, we = xs
            return acc + we[:, None] * _swiglu(u, w13, w2), None

        out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                              (mp["w13"], mp["w2"], w.T))
        return out

    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], ids, axis=0))
        for kind, lp in zip(cfg.layer_types, params["layers"]):
            u = norm(x, lp["ln_op"])
            x = x + (short_conv(u, lp["conv"]) if kind == "conv"
                     else attention(u, lp["attn"]))
            u = norm(x, lp["ln_ffn"])
            x = x + (experts(u, lp["moe"]) if "moe" in lp
                     else _swiglu(u, lp["ffn"]["w13"], lp["ffn"]["w2"]))
        x = norm(x, params["norm_f"])
        rows = x if n_rows is None else jax.lax.dynamic_slice_in_dim(
            x, start, n_rows)
        if "head" in params:
            return rows @ _f32(params["head"])
        return rows @ _f32(params["embed"]).T


def teacher_forced_deficits(params, n_head, prompt, output, pad_to,
                            cfg=None, limits=None, row_bucket=256):
    """For every emitted token, how far its reference logit lies under the
    reference's largest logit at that position, the emitted sequence fed as
    input (zero-padded to `pad_to`; causal, so the padding changes nothing).
    Only the emitted rows, in a window of a whole number of `row_bucket`
    rows, meet the head.

    One more entry follows the tokens': the request's MEAN deficit on the
    per-token limit's scale (x `logit_margin / logit_mean_margin`), so that
    the one limit a kind knows holds both: bf16 flips a near-tie between a
    token's 4th and 5th expert now and then, which moves that token's logits
    far more than rounding does, while a systematic fault (a stale
    convolution state, the wrong K/V head) moves EVERY token a little, which
    only the mean tells from the flips (the configuration file's
    `serve.logit_margin_why` has the readings). `cfg` and `limits` (the
    configuration file's `serve` group) default to what build_engine built
    from."""
    import jax.numpy as jnp
    import numpy as np

    cfg = cfg or _BUILT["config"]
    limits = limits or _BUILT["serve"]
    if n_head != cfg.num_attention_heads:
        raise ValueError(f"n_head {n_head} is not the built model's "
                         f"{cfg.num_attention_heads}")
    seq = list(prompt) + list(output)
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq)] = seq
    n_rows = min(pad_to, -(-len(output) // row_bucket) * row_bucket)
    start = min(len(prompt) - 1, pad_to - n_rows)
    skip = len(prompt) - 1 - start
    picked_ids = np.zeros((n_rows,), np.int32)
    picked_ids[skip:skip + len(output)] = output
    d = _deficits_fn(cfg, n_rows)(params, jnp.asarray(ids), np.int32(start),
                                  jnp.asarray(picked_ids))
    d = np.asarray(d)[skip:skip + len(output)]
    scale = float(limits["logit_margin"]) / float(limits["logit_mean_margin"])
    return np.append(d, d.mean() * scale)


@functools.lru_cache(maxsize=None)
def _deficits_fn(cfg, n_rows):
    """Compiled once per model configuration and row window."""
    import jax
    import jax.numpy as jnp

    def deficits(params, ids, start, picked_ids):
        rows = reference_logits(params, ids, cfg, start, n_rows)
        picked = jnp.take_along_axis(rows, picked_ids[:, None], -1)[:, 0]
        return rows.max(-1) - picked

    return jax.jit(deficits)
