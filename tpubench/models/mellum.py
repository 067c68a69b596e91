"""Mellum2-12B-A2.5B-Instruct (`model_type` `mellum`; JetBrains/Mellum2-12B-
A2.5B-Instruct config.json): how the benchmark builds the engine from a
configuration file, what a decode step cannot do without (from shapes and the
program's routing counters, never from what an implementation happens to
read), and the plain float32 reference.

The block (`N` RMSNorm with a weight and no bias, eps `rms_norm_eps`; no
projection has a bias). Layer `l` of `layer_types`:

    h = x + Attn_l(N(x))              x' = h + MoE_l(N(h))
    logits = N(x_last) . W_head       (the head untied)

- `Attn_l`: `q = u W_q` (`num_attention_heads` x `head_dim`), `k`, `v` of
  `num_key_value_heads` x `head_dim`; `q` and `k` RMS-normalised per head (own
  gains) BEFORE rotary; rotary over the whole head, half-split pairing, by the
  layer's type; scores `/ sqrt(head_dim)`, softmax over the visible keys; query
  head `h` reads K/V head `h // G`; `W_o`.
  `sliding_attention`: key `j` is visible to query `i` iff `i - sliding_window
  < j <= i`; `inv_freq_i = theta^(-2i/D)`.
  `full_attention`: `j <= i`; YaRN: with `dim(r) = D ln(original / (2 pi r)) /
  (2 ln theta)`, `low = max(floor(dim(beta_fast)), 0)`, `high =
  min(ceil(dim(beta_slow)), D - 1)`, `ramp_i = clip((i - low) / (high - low),
  0, 1)`: `inv_freq_i = theta^(-2i/D) ((1 - ramp_i) + ramp_i / factor)`, and
  `cos`, `sin` both times `attention_factor`.
- `MoE_l` (every layer: `mlp_layer_types` is `sparse` throughout, the
  published `intermediate_size` is no layer's): `s = softmax(u W_r)` in
  float32, the `num_experts_per_tok` largest chosen, weights `s_i / sum of the
  chosen`, no selection bias, no shared expert; expert `e`: `W2_e (silu(W1_e
  u) * W3_e u)`.

The reference computes exactly that over the whole sequence: the mask built
from positions, K/V heads repeated, every expert for every token with the
router's weight (0 where not chosen), no cache, kernel or batching; it is
written from the equations above and imports nothing of the program's block.
Departures from the published description are in the configuration file
(`reduced`, `assumed`): depth, the q/k norm, the rotary pairing, a serving
`max_seq_len`, the multi-token-prediction head left out. The program stores
the three attention projections side by side (`wqkv`) and an expert's gate
and up projections side by side (`w13`); the reference reads the program's
tree.
"""
from __future__ import annotations

import functools
import math
import statistics

PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "num_experts", "num_experts_per_tok", "norm_topk_prob",
             "sliding_window", "max_position_embeddings", "rms_norm_eps",
             "tie_word_embeddings")

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
    h, heads, d = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    kv = c["num_key_value_heads"] * d
    types = layer_types(c)
    return {
        "attn": h * (heads * d + 2 * kv) + heads * d * h + 2 * d,
        "norms": 2 * h,
        "expert": 3 * h * c["moe_intermediate_size"],
        "router": h * c["num_experts"],
        "embed": c["vocab_size"] * h,
        "n_full": sum(t == "full_attention" for t in types),
        "n_win": sum(t == "sliding_attention" for t in types),
        "layers": len(types), "kv_row": kv,
    }


def param_count(config):
    """3 794 968 832 at the first 8 layers (6 sliding + 2 full attentions,
    8 x 64 experts), the whole vocabulary, embedding and untied head."""
    w = _widths(config)
    return (w["layers"] * (w["attn"] + w["norms"] + w["router"]
                           + config["num_experts"] * w["expert"])
            + config["hidden_size"] + 2 * w["embed"])


def cache_blocks_per_seq(config, ctx):
    """Blocks a sequence of `ctx` tokens holds at most: every block of the
    full group, `ceil(window / BS) + 2` of each window group."""
    w, bs = _widths(config), config["serve"]["block_size"]
    g = w["n_full"]
    return math.ceil(ctx / bs) + (w["n_win"] // g) * (
        math.ceil(config["sliding_window"] / bs) + 2)


def kv_row_tokens(config, ctx_tokens, batch):
    """Token rows (of one K and one V row each) whose reading the
    mathematics of one decode step needs, over `batch` sequences of
    `ctx_tokens` tokens together: `ctx` a full layer, `min(ctx, window)` a
    window layer."""
    w = _widths(config)
    seen = min(ctx_tokens, batch * config["sliding_window"])
    return w["n_full"] * ctx_tokens + w["n_win"] * seen


def _attend_flops(config, rows):
    """Scores and values per query head over `rows` (layer, token) rows."""
    return 2 * config["num_attention_heads"] * rows * 2 * config["head_dim"]


def decode_least(config, ctx_tokens, batch, experts_hit, itemsize):
    """(flops, bytes) one decode step cannot do without. Bytes: every weight
    outside the routed experts once (attention, norms, the untied head; of
    the embedding a row per sequence), the routers in float32,
    `experts_hit` (expert, layer) pairs' three matrices, the K and V rows of
    `kv_row_tokens`. Flops: 2 per active parameter per sequence, plus scores
    and values per query head and visible token."""
    c, w = config, _widths(config)
    h = c["hidden_size"]
    fixed = w["layers"] * (w["attn"] + w["norms"]) + h + w["embed"]
    rows = kv_row_tokens(c, ctx_tokens, batch)
    nbytes = (fixed * itemsize + w["layers"] * w["router"] * 4
              + experts_hit * w["expert"] * itemsize
              + batch * h * itemsize + rows * 2 * w["kv_row"] * itemsize)
    active = fixed + w["layers"] * (c["num_experts_per_tok"] * w["expert"]
                                    + w["router"])
    return 2 * active * batch + _attend_flops(c, rows), nbytes


def kernels_least(config, ctx_tokens, batch, experts_hit, itemsize):
    """(flops, bytes) the custom calls of ONE decode step cannot do without:
    the paged attention launches (the K and V rows of `kv_row_tokens`) and
    the grouped matmuls (the hit experts' three matrices once; 2 flops per
    expert parameter per assignment)."""
    c, w = config, _widths(config)
    rows = kv_row_tokens(c, ctx_tokens, batch)
    nbytes = (rows * 2 * w["kv_row"] * itemsize
              + experts_hit * w["expert"] * itemsize)
    flops = _attend_flops(c, rows) + 2 * w["layers"] * batch * c[
        "num_experts_per_tok"] * w["expert"]
    return flops, nbytes


def attend_least(config, ctx_tokens, batch, itemsize):
    """(flops, bytes) of one decode step's paged attention launches alone."""
    rows = kv_row_tokens(config, ctx_tokens, batch)
    return (_attend_flops(config, rows),
            rows * 2 * _widths(config)["kv_row"] * itemsize)


def _window(run):
    """(median live context, (expert, layer) pairs hit a step) of the
    window, from the steps' records and the program's routing counters."""
    ctx = run.samples.get("step_ctx_tokens")
    if not ctx or "close" not in run.counters:
        return None
    layer_steps = run.counter_delta("serve/moe/layer_steps")
    if not layer_steps:
        return None
    return (statistics.median(ctx),
            run.counter_delta("serve/moe/experts_hit") / layer_steps
            * _widths(run.config)["layers"])


def _itemsize(run):
    return {"float32": 4, "bfloat16": 2}[run.config["serve"]["weight_dtype"]]


def _decode_steps(run):
    """Decode programs in the trace: the most frequent `jit__unknown`, found
    as `decode_roofline.*` finds it."""
    tr = run.reduced_trace()
    if tr is None:
        return 0
    return len(tr.module_events("jit__unknown", min(tr.devices), True))


def least_decode(run, n_events):
    """(flops, bytes) of `n_events` decode programs."""
    win = _window(run)
    if win is None:
        return None
    flops, nbytes = decode_least(run.config, win[0],
                                 run.config["serve"]["max_batch"], win[1],
                                 _itemsize(run))
    return n_events * flops, n_events * nbytes


def least_kernels(run, n_events):
    """(flops, bytes) of the trace's custom calls: they are the decode
    steps' (the prefills are over before the window opens), so many steps'
    as the trace holds decode programs, not `n_events` over a count of calls
    a step, which is the compiler's to change."""
    win, steps = _window(run), _decode_steps(run)
    if win is None or not steps:
        return None
    flops, nbytes = kernels_least(run.config, win[0],
                                  run.config["serve"]["max_batch"], win[1],
                                  _itemsize(run))
    return steps * flops, steps * nbytes


def least_attend(run, n_events):
    """(flops, bytes) of the trace's paged attention launches, as many
    steps' as the trace holds decode programs."""
    win, steps = _window(run), _decode_steps(run)
    if win is None or not steps:
        return None
    flops, nbytes = attend_least(run.config, win[0],
                                 run.config["serve"]["max_batch"],
                                 _itemsize(run))
    return steps * flops, steps * nbytes


# -- the system under test ---------------------------------------------------

def model_config(config):
    from paddle_tpu.text.models.mellum import MellumConfig

    full = config["rope_parameters"]["full_attention"]
    sliding = config["rope_parameters"]["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default") \
            or full["rope_theta"] != sliding["rope_theta"]:
        raise ValueError("rope_parameters: yarn over the full layers, the "
                         "default rule over the sliding, one theta")
    return MellumConfig(
        dtype=config["serve"]["weight_dtype"],
        layer_types=layer_types(config),
        rope_theta=float(full["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original_max_position_embeddings=full[
            "original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        initializer_range=config.get("initializer_range", 0.02),
        qk_norm_init=config.get("qk_norm_init", 1.0),
        **{k: config[k] for k in PUBLISHED})


def build_engine(config, seed):
    """LLMEngine(model.eval()) with the serving settings the file states;
    weights drawn on the device from the seed."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import LLMEngine
    from paddle_tpu.text.models.mellum import MellumForCausalLM

    s = config["serve"]
    cfg = model_config(config)
    paddle.seed(int(seed) % 2147483647)
    model = MellumForCausalLM(cfg)
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


def rotary_inv_freq(cfg, kind):
    """(inv_freq [D/2], the factor on cos and sin) of a layer type, from the
    docstring's equations, in float64 numpy."""
    import numpy as np

    d = cfg.head_dim
    inv = np.asarray([cfg.rope_theta ** (-2.0 * i / d)
                      for i in range(d // 2)])
    if kind == "sliding_attention":
        return inv, 1.0
    original = cfg.yarn_original_max_position_embeddings

    def dim(turns):
        return d * math.log(original / (2 * math.pi * turns)) / (
            2 * math.log(cfg.rope_theta))

    low = max(math.floor(dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim(cfg.yarn_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (inv * ((1.0 - ramp) + ramp / cfg.yarn_factor),
            cfg.yarn_attention_factor)


def reference_hidden(params, ids, cfg, q_block=256):
    """The forward pass up to the final norm, in plain float32 jax.numpy at
    `highest` matmul precision, from the equations of the module's
    docstring. `params` is the engine's own tree (text/models/mellum.py: a
    list of layers, each its own tree), cast up a matrix (for `w13` half a
    matrix) and an expert at a time, one layer after another; attention
    runs over the whole sequence in blocks of `q_block` queries, the mask
    built from positions. ids [S] -> N(x) [S, hidden]."""
    import jax
    import jax.numpy as jnp

    eps = cfg.rms_norm_eps
    heads, kv_heads, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.head_dim)
    s = ids.shape[0]
    q_block = min(q_block, s)
    if s % q_block:
        raise ValueError(f"{s} positions in query blocks of {q_block}")
    pos = jnp.arange(s)

    def norm(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * _f32(w)

    def rotary(x, kind):
        """R_t over the last dim of x [S, heads, d], pairing dim i with
        i + d/2."""
        inv, factor = rotary_inv_freq(cfg, kind)
        ang = (pos.astype(jnp.float32)[:, None]
               * jnp.asarray(inv, jnp.float32))[:, None, :]
        cos, sin = factor * jnp.cos(ang), factor * jnp.sin(ang)
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], -1)

    def attention(u, ap, kind):
        qkv = u @ _f32(ap["wqkv"])
        q = qkv[:, :heads * d].reshape(s, heads, d)
        k = qkv[:, heads * d:(heads + kv_heads) * d].reshape(s, kv_heads, d)
        v = qkv[:, (heads + kv_heads) * d:].reshape(s, kv_heads, d)
        q = rotary(norm(q, ap["q_norm"]), kind)
        k = rotary(norm(k, ap["k_norm"]), kind)
        # query head h reads K/V head h // G
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i, q_block)
            scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
            behind = (i + jnp.arange(q_block))[:, None] - pos[None, :]
            seen = behind >= 0
            if kind == "sliding_attention":
                seen = seen & (behind < cfg.sliding_window)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        out = jax.lax.map(block, jnp.arange(0, s, q_block))
        return out.reshape(s, heads * d) @ _f32(ap["wo"])

    def experts(u, mp):
        scores = jax.nn.softmax(u @ _f32(mp["router_w"]), -1)    # [S, E]
        picked, chosen = jax.lax.top_k(scores, cfg.num_experts_per_tok)
        weight = picked / picked.sum(-1, keepdims=True)
        # w[t, e]: the weight of expert e for token t, 0 if not chosen
        w = jnp.zeros_like(scores).at[
            jnp.arange(s)[:, None], chosen].set(weight)

        def one(acc, xs):
            w13, w2, we = xs
            half = w13.shape[-1] // 2
            y = (_silu(u @ _f32(w13[:, :half])) * (u @ _f32(w13[:, half:]))) \
                @ _f32(w2)
            return acc + we[:, None] * y, None

        out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                              (mp["w13"], mp["w2"], w.T))
        return out

    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], ids, axis=0))
        for kind, lp in zip(cfg.layer_types, params["layers"]):
            x = x + attention(norm(x, lp["ln_attn"]), lp["attn"], kind)
            x = x + experts(norm(x, lp["ln_ffn"]), lp["moe"])
        return norm(x, params["norm_f"])


def reference_logits(params, ids, cfg, start=0, n_rows=None, q_block=256):
    """`reference_hidden` through the untied head: ids [S] -> logits
    [n_rows, V] of rows `start : start + n_rows` (all by default)."""
    import jax

    x = reference_hidden(params, ids, cfg, q_block)
    rows = x if n_rows is None else jax.lax.dynamic_slice_in_dim(
        x, start, n_rows)
    with jax.default_matmul_precision("highest"):
        return rows @ _f32(params["head"])


def teacher_forced_deficits(params, n_head, prompt, output, pad_to,
                            cfg=None, limits=None, row_bucket=256):
    """For every emitted token, how far its reference logit lies under the
    reference's largest logit at that position, the emitted sequence fed as
    input (zero-padded to `pad_to`; causal, so the padding changes nothing).
    Only the emitted rows, in a window of a whole number of `row_bucket`
    rows, meet the head, a slice of the vocabulary at a time.

    One more entry follows the tokens': the request's MEAN deficit on the
    per-token limit's scale (x `logit_margin / logit_mean_margin`), so that
    the one limit a kind knows holds both (tpubench/models/lfm2_moe.py has
    the reason; the configuration file's `serve.logit_margin_why` this
    cell's readings). `cfg` and `limits` (the configuration file's `serve`
    group) default to what build_engine built from."""
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
    print(f"[tpubench] mellum reference: {len(prompt)} prompt + {len(output)} "
          f"emitted tokens, largest token deficit {d.max():.5f}, the "
          f"request's mean {d.mean():.5f}", flush=True)
    scale = float(limits["logit_margin"]) / float(limits["logit_mean_margin"])
    return np.append(d, d.mean() * scale)


@functools.lru_cache(maxsize=None)
def _deficits_fn(cfg, n_rows, vocab_block=8192):
    """Compiled once per model configuration and row window. The head meets
    the rows `vocab_block` columns at a time (98 304 x 2304 float32 values
    and 2048 rows of logits would not fit beside a live engine)."""
    import jax
    import jax.numpy as jnp

    def deficits(params, ids, start, picked_ids):
        x = reference_hidden(params, ids, cfg)
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_rows)
        head = params["head"]
        width = math.gcd(head.shape[1], vocab_block)
        with jax.default_matmul_precision("highest"):
            largest = jax.lax.map(
                lambda c: (rows @ _f32(jax.lax.dynamic_slice_in_dim(
                    head, c, width, axis=1))).max(-1),
                jnp.arange(0, head.shape[1], width)).max(0)
            picked = jnp.einsum("rh,hr->r", rows,
                                _f32(jnp.take(head, picked_ids, axis=1)))
        return largest - picked

    return jax.jit(deficits)
