"""LongCat-Flash-Chat (meituan-longcat/LongCat-Flash-Chat config.json): how
the benchmark builds the engine from a configuration file, what a decode step
cannot do without (from shapes and the program's routing counters, never from
what an implementation happens to read), and the plain float32 reference.

One of the `num_layers` layers is a DOUBLE layer (`N` RMSNorm with a weight,
eps `rms_norm_eps`; no projection has a bias): two attentions, two dense FFNs
and one expert FFN whose output joins the residual stream a sub-layer later
(shortcut-connected MoE):

    h1 = x  + MLA_0(N(x))
    u1 = N(h1)
    m  = MoE(u1)
    h2 = h1 + FFN_0(u1)
    h3 = h2 + MLA_1(N(h2))
    x' = h3 + FFN_1(N(h3)) + m

MLA: `c_q = N(u W_qa)`, `q = a_q c_q W_qb` per head `[nope | rope]`, `[c_kv |
k_r] = u W_kva`, `c_kv = a_kv N(c_kv)`, rotary over `q`'s rope dims and `k_r`
(shared by the heads), `[k_nope | v] = c_kv W_kvb`, causal softmax of `q . k /
sqrt(nope + rope)`, times `W_o`; `a_q = (hidden_size / q_lora_rank)^0.5`
(`mla_scale_q_lora`), `a_kv = (hidden_size / kv_lora_rank)^0.5`
(`mla_scale_kv_lora`). MoE: `s = softmax(u W_r)` over `n_routed_experts +
zero_expert_num` outputs, the `moe_topk` largest of `s + b` chosen, weights
`routed_scaling_factor x s_i` (not renormalised); an id under
`n_routed_experts` is a SwiGLU of width `expert_ffn_hidden_size`, one above a
zero-compute expert (identity): `w_i u`. No shared expert. Final `N`, untied
head.

The chip's share (the configuration file's `share`): this chip holds
`n_routed_experts` (the file's, reduced) of the `published` experts from
`share.expert_first` on and a slice of the vocabulary; what experts held
elsewhere would add is left out, here as in the program. Departures from the
published description are in the configuration file (`reduced`, `assumed`).
"""
from __future__ import annotations

import functools
import math
import statistics

PUBLISHED = ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
             "num_layers", "num_attention_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
             "routed_scaling_factor", "max_position_embeddings",
             "rms_norm_eps", "rope_theta", "zero_expert_num",
             "zero_expert_type", "moe_topk", "vocab_size")

# what build_engine last built from (the model configuration and the file's
# `serve` group): the kinds hand teacher_forced_deficits the parameter tree
# and `n_head` only
_BUILT = {}


# -- counts from shapes ------------------------------------------------------

def _widths(config):
    c = config
    heads, h = c["num_attention_heads"], c["hidden_size"]
    mla = (h * c["q_lora_rank"]
           + c["q_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                         + c["qk_rope_head_dim"])
           + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
           + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                          + c["v_head_dim"])
           + heads * c["v_head_dim"] * h
           + 2 * h + c["q_lora_rank"] + c["kv_lora_rank"])
    outputs = c["published"]["n_routed_experts"] + c["zero_expert_num"]
    return {
        "mla": mla,
        "dense_ffn": 3 * h * c["ffn_hidden_size"],
        "expert": 3 * h * c["expert_ffn_hidden_size"],
        "router": h * outputs + outputs,
        "embed": c["vocab_size"] * h,
        "layers": c["num_layers"],
        "held": c["n_routed_experts"],
    }


def param_count(config):
    """What this chip holds: 5 172 749 312 at 4 double layers, 16 experts
    of each and 16384 rows of the vocabulary."""
    w = _widths(config)
    return (2 * w["embed"] + config["hidden_size"] + w["layers"] * (
        2 * w["mla"] + 2 * w["dense_ffn"] + w["router"]
        + w["held"] * w["expert"]))


def latent_row(config):
    """Values a token leaves in the cache, an attention."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def decode_least(config, ctx_tokens, batch, experts_hit, assignments,
                 itemsize):
    """(flops, bytes) one decode step cannot do without. Bytes: the weights
    outside the experts and the head's slice read once, `experts_hit`
    (expert, layer) pairs' three matrices, a row of the embedding per
    sequence, and every live token's latent row once an attention. Flops: 2
    per active parameter (`assignments` token-expert pairs a step meet an
    expert's), plus the absorbed attention's scores (row wide) and values
    (kv_lora_rank wide) per head and live token."""
    c, w = config, _widths(config)
    h, heads, attns = c["hidden_size"], c["num_attention_heads"], \
        2 * w["layers"]
    fixed = w["layers"] * 2 * (w["mla"] + w["dense_ffn"]) + h + w["embed"]
    nbytes = (fixed * itemsize + w["layers"] * w["router"] * 4
              + experts_hit * w["expert"] * itemsize
              + batch * h * itemsize
              + attns * latent_row(c) * itemsize * ctx_tokens)
    flops = (2 * (fixed + w["layers"] * w["router"]) * batch
             + 2 * assignments * w["expert"]
             + 2 * attns * heads * ctx_tokens * (latent_row(c)
                                                 + c["kv_lora_rank"]))
    return flops, nbytes


def least_decode(run, n_events):
    """(flops, bytes) of `n_events` decode programs: the live context is the
    median over the window's steps; the experts hit and the assignments a
    step are the window's means by the program's own routing counters
    (`serve/moe/*`, over the experts held here)."""
    ctx = run.samples.get("step_ctx_tokens")
    if not ctx or "close" not in run.counters:
        return None
    layer_steps = run.counter_delta("serve/moe/layer_steps")
    if not layer_steps:
        return None
    per_step = run.config["num_layers"] / layer_steps
    s = run.config["serve"]
    flops, nbytes = decode_least(
        run.config, statistics.median(ctx), s["max_batch"],
        run.counter_delta("serve/moe/experts_hit") * per_step,
        run.counter_delta("serve/moe/assignments") * per_step,
        {"float32": 4, "bfloat16": 2}[s["weight_dtype"]])
    return n_events * flops, n_events * nbytes


# -- the system under test ---------------------------------------------------

def model_config(config):
    from paddle_tpu.text.models.longcat_flash import LongcatFlashConfig

    return LongcatFlashConfig(
        dtype=config["serve"]["weight_dtype"],
        n_routed_experts=config["published"]["n_routed_experts"],
        expert_first=config["share"]["expert_first"],
        experts_held=config["n_routed_experts"],
        **{k: config[k] for k in PUBLISHED})


def build_engine(config, seed):
    """LLMEngine(model.eval()) with the serving settings the file states;
    weights drawn on the device from the seed."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import LLMEngine
    from paddle_tpu.text.models.longcat_flash import LongcatFlashForCausalLM

    s = config["serve"]
    cfg = model_config(config)
    paddle.seed(int(seed) % 2147483647)
    model = LongcatFlashForCausalLM(cfg)
    model.eval()
    _BUILT.update(config=cfg, serve=s)
    return LLMEngine(model, max_batch=s["max_batch"],
                     block_size=s["block_size"],
                     num_blocks=s.get("num_blocks"), dtype=s["kv_dtype"],
                     spec_k=s["spec_k"], prefix_cache=s["prefix_cache"],
                     max_seq_len=config["n_positions"])


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


def reference_route(u, router_w, router_b, cfg):
    """(chosen ids [S, k] over all `n_routed_experts + zero_expert_num`
    outputs, their weights [S, k]): softmax scores, chosen by score + bias,
    weighed by `routed_scaling_factor` x score as it is."""
    import jax

    s = jax.nn.softmax(u @ _f32(router_w), axis=-1)
    _, chosen = jax.lax.top_k(s + _f32(router_b), cfg.moe_topk)
    return chosen, cfg.routed_scaling_factor * jax.numpy.take_along_axis(
        s, chosen, -1)


def reference_moe(u, router_w, router_b, w13, w2, cfg, first=0):
    """The expert FFN over tokens u [S, H], or a chip's part of it: `w13
    [E, H, 2F]`, `w2 [E, F, H]` are experts `first .. first + E` of the
    layer's `n_routed_experts`. Every held expert's SwiGLU for every token
    with the router's weight (zero where the expert was not among the
    token's picks), plus `w_i u` for each pick of a zero-compute expert;
    picks of experts not held add nothing."""
    import jax
    import jax.numpy as jnp

    s = u.shape[0]
    chosen, weight = reference_route(u, router_w, router_b, cfg)
    # w[t, e]: the weight of router output e for token t, 0 if not chosen
    w = jnp.zeros((s, router_w.shape[-1]), jnp.float32).at[
        jnp.arange(s)[:, None], chosen].set(weight)

    def one(acc, xs):
        e13, e2, we = xs
        return acc + we[:, None] * _swiglu(u, e13, e2), None

    held = w[:, first:first + w13.shape[0]]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (w13, w2, held.T))
    return out + w[:, cfg.n_routed_experts:].sum(-1, keepdims=True) * u


def reference_logits(params, ids, cfg, start=0, n_rows=None, q_block=256):
    """The forward pass in plain float32 jax.numpy at `highest` matmul
    precision, from the equations: non-absorbed attention for every
    position, every held expert's SwiGLU for every token with the router's
    weight, no cache, kernel or batching. `params` is the engine's own tree
    (text/models/longcat_flash.py: `layers` with a leading layer axis, the
    two attentions and the two dense FFNs of a layer side by side in lists),
    cast up a matrix (for `w13` half a matrix) and an expert at a time, one
    layer after another; attention runs in blocks of `q_block` queries; only
    rows `start : start + n_rows` meet the head, so that 4096 positions fit
    beside a live engine. ids [S] -> logits [n_rows, V]."""
    import jax
    import jax.numpy as jnp

    eps = cfg.rms_norm_eps
    heads, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim)
    vdim, rank = cfg.v_head_dim, cfg.kv_lora_rank
    a_q = math.sqrt(cfg.hidden_size / cfg.q_lora_rank) \
        if cfg.mla_scale_q_lora else 1.0
    a_kv = math.sqrt(cfg.hidden_size / rank) \
        if cfg.mla_scale_kv_lora else 1.0
    s = ids.shape[0]
    q_block = min(q_block, s)
    pos = jnp.arange(s, dtype=jnp.float32)

    def norm(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * _f32(w)

    def rotary(x):
        """R_t over the last dim, pairing dim i with i + rope/2."""
        half = rope // 2
        inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[:, None] * inv                              # [S, half]
        ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (half,))
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                                x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)

    def mla(u, ap):
        c_q = norm(u @ _f32(ap["wq_a"]), ap["q_norm"])
        q = a_q * (c_q @ _f32(ap["wq_b"])).reshape(s, heads, nope + rope)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:])], -1)
        kv = u @ _f32(ap["wkv_a"])
        c_kv = a_kv * norm(kv[:, :rank], ap["kv_norm"])
        k_rope = rotary(kv[:, rank:])                         # [S, rope]
        kvb = (c_kv @ _f32(ap["wkv_b"])).reshape(s, heads, nope + vdim)
        k = jnp.concatenate(
            [kvb[..., :nope],
             jnp.broadcast_to(k_rope[:, None], (s, heads, rope))], -1)
        v = kvb[..., nope:]

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i, q_block)
            scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(
                nope + rope)
            seen = (i + jnp.arange(q_block))[:, None] >= jnp.arange(s)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        out = jax.lax.map(block, jnp.arange(0, s, q_block))
        return out.reshape(s, heads * vdim) @ _f32(ap["wo"])

    def double_layer(x, lp):
        (ap0, ap1), (f0, f1) = lp["attn"], lp["ffn"]
        h1 = x + mla(norm(x, ap0["ln1"]), ap0)
        u1 = norm(h1, ap0["ln2"])
        m = reference_moe(u1, lp["router_w"], lp["router_b"], lp["w13"],
                          lp["w2"], cfg, cfg.expert_first)
        h2 = h1 + _swiglu(u1, f0["w13"], f0["w2"])
        h3 = h2 + mla(norm(h2, ap1["ln1"]), ap1)
        return h3 + _swiglu(norm(h3, ap1["ln2"]), f1["w13"], f1["w2"]) + m

    if s % q_block:
        raise ValueError(f"{s} positions in query blocks of {q_block}")
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], ids, axis=0))
        # one layer after another (a scan only so that it compiles once
        # and no layer's matrices are cast up before their turn)
        x, _ = jax.lax.scan(lambda x, lp: (double_layer(x, lp), None), x,
                            params["layers"])
        x = norm(x, params["norm_f"])
        rows = x if n_rows is None else jax.lax.dynamic_slice_in_dim(
            x, start, n_rows)
        return rows @ _f32(params["head"])


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
    token's 12th and 13th pick now and then, which moves that token's logits
    far more than rounding does, while a systematic fault moves EVERY token
    a little, which only the mean tells from the flips (the configuration
    file's `serve.logit_margin_why` has the readings). `cfg` and `limits`
    (the configuration file's `serve` group) default to what build_engine
    built from."""
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
