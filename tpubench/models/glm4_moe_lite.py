"""GLM-4.7-Flash (`model_type` `glm4_moe_lite`; zai-org/GLM-4.7-Flash
config.json): how the benchmark builds the engine from a configuration
file, what a decode step cannot do without (from shapes and the program's
routing counters, never from what an implementation happens to read), and
the plain float32 reference.

The block (`N` RMSNorm with a weight and no bias, no projection has a bias):
`h = x + MLA(N(x))`, `x' = h + FFN(N(h))`; MLA with a low-rank query
(`q_lora_rank`), a latent `c_kv` of `kv_lora_rank` and one rotated key of
`qk_rope_head_dim` shared by all heads; the first `first_k_dense_replace`
FFNs dense SwiGLU, the others `num_experts_per_tok` of `n_routed_experts`
SwiGLUs chosen by sigmoid scores plus a selection bias, renormalised and
scaled, plus one shared expert. Departures from the published description
are in the configuration file (`reduced`, `assumed`): depth, no
multi-token-prediction module, half-split rotary pairing, a serving
`max_seq_len`.
"""
from __future__ import annotations

import functools
import math
import statistics

PUBLISHED = ("vocab_size", "hidden_size", "num_hidden_layers",
             "num_attention_heads", "q_lora_rank", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "intermediate_size", "moe_intermediate_size", "n_routed_experts",
             "n_shared_experts", "num_experts_per_tok",
             "routed_scaling_factor", "first_k_dense_replace",
             "max_position_embeddings", "rms_norm_eps", "rope_theta")

# what build_engine last built from (the model configuration and the file's
# `serve` group): the kinds hand teacher_forced_deficits the parameter tree
# and `n_head` only
_BUILT = {}


# -- counts from shapes ------------------------------------------------------

def _widths(config):
    c = config
    heads = c["num_attention_heads"]
    h = c["hidden_size"]
    mla = (h * c["q_lora_rank"]
           + c["q_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                         + c["qk_rope_head_dim"])
           + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
           + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                          + c["v_head_dim"])
           + heads * c["v_head_dim"] * h
           + 2 * h + c["q_lora_rank"] + c["kv_lora_rank"])
    return {
        "mla": mla,
        "dense_ffn": 3 * h * c["intermediate_size"],
        "expert": 3 * h * c["moe_intermediate_size"],
        "router": h * c["n_routed_experts"] + c["n_routed_experts"],
        "embed": c["vocab_size"] * h,
        "n_dense": c["first_k_dense_replace"],
        "n_moe": c["num_hidden_layers"] - c["first_k_dense_replace"],
    }


def param_count(config):
    """4 530 936 960 at 1 + 6 layers, every expert and the whole
    vocabulary held."""
    w = _widths(config)
    layers = w["n_dense"] + w["n_moe"]
    return (2 * w["embed"] + config["hidden_size"] + layers * w["mla"]
            + w["n_dense"] * w["dense_ffn"]
            + w["n_moe"] * ((config["n_routed_experts"] + 1) * w["expert"]
                            + w["router"]))


def latent_row(config):
    """Values a token leaves in the cache, a layer."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def decode_least(config, ctx_tokens, batch, experts_hit, itemsize):
    """(flops, bytes) one decode step cannot do without. Bytes: the weights
    outside the routed experts and the head read once, `experts_hit`
    (expert, layer) pairs' three matrices, a row of the embedding per
    sequence, and every live token's latent row once a layer. Flops: 2 per
    active parameter per sequence, plus the absorbed attention's scores
    (row wide) and values (kv_lora_rank wide) per head and live token."""
    c, w = config, _widths(config)
    layers = w["n_dense"] + w["n_moe"]
    h, heads = c["hidden_size"], c["num_attention_heads"]
    fixed = (layers * w["mla"] + w["n_dense"] * w["dense_ffn"]
             + w["n_moe"] * w["expert"] + h + w["embed"])
    nbytes = (fixed * itemsize + w["n_moe"] * w["router"] * 4
              + experts_hit * w["expert"] * itemsize
              + batch * h * itemsize
              + layers * latent_row(c) * itemsize * ctx_tokens)
    active = (fixed + w["n_moe"] * (c["num_experts_per_tok"] * w["expert"]
                                    + w["router"]))
    flops = 2 * active * batch + 2 * layers * heads * ctx_tokens * (
        latent_row(c) + c["kv_lora_rank"])
    return flops, nbytes


def least_decode(run, n_events):
    """(flops, bytes) of `n_events` decode programs: the live context is the
    median over the window's steps, the experts hit a step the window's mean
    by the program's own routing counters (`serve/moe/*`)."""
    ctx = run.samples.get("step_ctx_tokens")
    if not ctx or "close" not in run.counters:
        return None
    layer_steps = run.counter_delta("serve/moe/layer_steps")
    if not layer_steps:
        return None
    n_moe = _widths(run.config)["n_moe"]
    hit = run.counter_delta("serve/moe/experts_hit") / layer_steps * n_moe
    s = run.config["serve"]
    flops, nbytes = decode_least(
        run.config, statistics.median(ctx), s["max_batch"], hit,
        {"float32": 4, "bfloat16": 2}[s["weight_dtype"]])
    return n_events * flops, n_events * nbytes


# -- the system under test ---------------------------------------------------

def model_config(config):
    from paddle_tpu.text.models.glm4_moe_lite import Glm4MoeLiteConfig

    return Glm4MoeLiteConfig(dtype=config["serve"]["weight_dtype"],
                             **{k: config[k] for k in PUBLISHED})


def build_engine(config, seed):
    """LLMEngine(model.eval()) with the serving settings the file states;
    weights drawn on the device from the seed."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import LLMEngine
    from paddle_tpu.text.models.glm4_moe_lite import Glm4MoeLiteForCausalLM

    s = config["serve"]
    cfg = model_config(config)
    paddle.seed(int(seed) % 2147483647)
    model = Glm4MoeLiteForCausalLM(cfg)
    model.eval()
    _BUILT.update(config=cfg, serve=s)
    return LLMEngine(model, max_batch=s["max_batch"],
                     block_size=s["block_size"],
                     num_blocks=s.get("num_blocks"), dtype=s["kv_dtype"],
                     spec_k=s["spec_k"], prefix_cache=s["prefix_cache"],
                     max_seq_len=config["n_positions"])


# -- the plain reference -----------------------------------------------------

def reference_logits(params, ids, cfg, start=0, n_rows=None, q_block=512):
    """The forward pass in plain float32 jax.numpy at `highest` matmul
    precision, from the equations: non-absorbed attention for every
    position, every expert's SwiGLU for every token with the router's
    weight (zero where the expert was not among the token's four), no
    cache, kernel or batching. `params` is the engine's own tree
    (text/models/glm4_moe_lite.py: `dense` and `moe` stacks with a leading
    layer axis, experts' gate and up projections side by side in `w13`),
    cast up a layer and an expert at a time; attention runs in blocks of
    `q_block` queries; only rows `start : start + n_rows` meet the head, so
    that 4096 positions fit beside a live engine. ids [S] -> logits
    [n_rows, V]."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)          # noqa: E731
    eps = cfg.rms_norm_eps
    heads, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim)
    vdim, rank = cfg.v_head_dim, cfg.kv_lora_rank
    s = ids.shape[0]
    q_block = min(q_block, s)
    pos = jnp.arange(s, dtype=jnp.float32)

    def norm(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * f32(w)

    def rotary(x):
        """R_t over the last dim, pairing dim i with i + rope/2."""
        half = rope // 2
        inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[:, None] * inv                              # [S, half]
        ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (half,))
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                                x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)

    def silu(x):
        return x / (1.0 + jnp.exp(-x))

    def swiglu(u, w13, w2):
        w13 = f32(w13)
        half = w13.shape[-1] // 2
        return (silu(u @ w13[:, :half]) * (u @ w13[:, half:])) @ f32(w2)

    def mla(u, ap):
        c_q = norm(u @ f32(ap["wq_a"]), ap["q_norm"])
        q = (c_q @ f32(ap["wq_b"])).reshape(s, heads, nope + rope)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:])], -1)
        kv = u @ f32(ap["wkv_a"])
        c_kv = norm(kv[:, :rank], ap["kv_norm"])
        k_rope = rotary(kv[:, rank:])                         # [S, rope]
        kvb = (c_kv @ f32(ap["wkv_b"])).reshape(s, heads, nope + vdim)
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
        return out.reshape(s, heads * vdim) @ f32(ap["wo"])

    def experts(u, lp):
        scores = jax.nn.sigmoid(u @ f32(lp["router_w"]))      # [S, E]
        _, chosen = jax.lax.top_k(scores + f32(lp["router_b"]),
                                  cfg.num_experts_per_tok)
        picked = jnp.take_along_axis(scores, chosen, -1)
        weight = cfg.routed_scaling_factor * picked / (
            picked.sum(-1, keepdims=True) + 1e-20)
        # w[t, e]: the weight of expert e for token t, 0 if not chosen
        w = jnp.zeros_like(scores).at[
            jnp.arange(s)[:, None], chosen].set(weight)

        def one(acc, xs):
            w13, w2, we = xs
            return acc + we[:, None] * swiglu(u, w13, w2), None

        out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                              (lp["w13"], lp["w2"], w.T))
        return out + swiglu(u, lp["shared_w13"], lp["shared_w2"])

    def dense_layer(x, lp):
        h = x + mla(norm(x, lp["attn"]["ln1"]), lp["attn"])
        return h + swiglu(norm(h, lp["attn"]["ln2"]), lp["w13"],
                          lp["w2"]), None

    def moe_layer(x, lp):
        h = x + mla(norm(x, lp["attn"]["ln1"]), lp["attn"])
        return h + experts(norm(h, lp["attn"]["ln2"]), lp), None

    if s % q_block:
        raise ValueError(f"{s} positions in query blocks of {q_block}")
    with jax.default_matmul_precision("highest"):
        x = f32(jnp.take(params["embed"], ids, axis=0))
        # one layer after another (a scan only so that it compiles once)
        x, _ = jax.lax.scan(dense_layer, x, params["dense"])
        x, _ = jax.lax.scan(moe_layer, x, params["moe"])
        x = norm(x, params["norm_f"])
        rows = x if n_rows is None else jax.lax.dynamic_slice_in_dim(
            x, start, n_rows)
        return rows @ f32(params["head"])


def teacher_forced_deficits(params, n_head, prompt, output, pad_to,
                            cfg=None, limits=None, row_bucket=256):
    """For every emitted token, how far its reference logit lies under the
    reference's largest logit at that position, the emitted sequence fed as
    input (zero-padded to `pad_to`; causal, so the padding changes nothing).
    Only the emitted rows, in a window of a whole number of `row_bucket`
    rows, meet the head.

    One more entry follows the tokens': the request's MEAN deficit on the
    per-token limit's scale (x `logit_margin / logit_mean_margin`), so that
    the one limit a kind knows holds both. Why two: in bf16 a near-tie
    between a token's 4th and 5th expert flips now and then, which moves
    that token's logits far more than rounding does, so a few tokens in a
    hundred lie far out while the typical token lies at 0; a systematic
    fault (a wrong rotary pairing, blocks read one off) moves EVERY token a
    little, which only the mean tells from the flips (the configuration
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
