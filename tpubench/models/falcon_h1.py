"""Falcon-H1-34B-Instruct (`model_type` `falcon_h1`; tiiuae/Falcon-H1-34B-
Instruct config.json): how the benchmark builds the engine from a
configuration file, what a decode step cannot do without (from shapes and
the window's own records, never from what an implementation happens to
read), and the plain float32 reference.

The block (`N` RMSNorm with a weight and no bias, eps `rms_norm_eps`; no
projection has a bias; every multiplier a published constant):

    x_0 = E[ids] * embedding_multiplier
    u   = N_in(x)
    x'  = x + Attn(u * attention_in_multiplier) * attention_out_multiplier
            + Mamba(u * ssm_in_multiplier) * ssm_out_multiplier
    x'' = x' + W_d (silu(N_ff(x') W_g * m_g) * (N_ff(x') W_u)) * m_d
    logits = N_f(x) W_head * lm_head_multiplier      ((m_g, m_d) = mlp_multipliers)

- `Attn`: `q = u W_q` (`num_attention_heads` x `head_dim`), `k = (u W_k) *
  key_multiplier`, `v = u W_v` (`num_key_value_heads` x `head_dim`); rotary
  over the whole head, `inv_freq_i = rope_theta^(-2i/D)`, dim `i` paired with
  `i + D/2`; scores `/ sqrt(head_dim)`, causal softmax; query head `h` reads
  K/V head `h // G`; `W_o`.
- `Mamba`: `p = u' W_in`, split in order into z (`mamba_d_ssm`), x
  (`mamba_d_ssm`), B and C (`mamba_n_groups x mamba_d_state` each) and dt
  (`mamba_n_heads`), each part times its entry of `ssm_multipliers`; `[x | B |
  C] = silu(b + sum_j w_j * [x | B | C]_{t-K+1+j})`, `K = mamba_d_conv`, zero
  before position 0; `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`; per
  head `h` (of `mamba_d_head` values, group `g = h // (heads / groups)`):
  `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T` (`S` [d_head, d_state], zero
  before position 0), `y_t = S_t C_t + D x_t`; then `y * silu(z)`,
  RMS-normalised over each of the `mamba_n_groups` groups of channels, times
  its gain; `W_out`.

The reference computes exactly that over the whole sequence: attention with
the mask built from positions and K/V heads repeated, the convolution as a
sum of shifted rows, and the recurrence SEQUENTIALLY, one position after
another in `S`'s published layout; no cache, chunking, kernel or batching. It
is written from the equations above and imports nothing of the program's
block. Departures from the published description are in the configuration
file (`reduced`, `assumed`): depth, the rotary pairing, the split order, how
the weights are drawn, a serving `max_seq_len`. The program stores the three
attention projections side by side (`wqkv`), the gate and up projections side
by side (`w13`), and the taps `[K, channels]`; the reference reads the
program's tree.
"""
from __future__ import annotations

import functools
import statistics

PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "rms_norm_eps", "max_position_embeddings", "tie_word_embeddings",
    "attention_in_multiplier", "attention_out_multiplier", "key_multiplier",
    "embedding_multiplier", "lm_head_multiplier", "mlp_multipliers",
    "ssm_in_multiplier", "ssm_multipliers", "ssm_out_multiplier",
    "mamba_conv_bias", "mamba_d_conv", "mamba_d_head",
    "mamba_d_ssm", "mamba_d_state", "mamba_n_groups", "mamba_n_heads",
    "mamba_norm_before_gate", "mamba_rms_norm")

# what build_engine last built from (the model configuration and the file's
# `serve` group): the kinds hand teacher_forced_deficits the parameter tree
# and `n_head` only
_BUILT = {}


# -- counts from shapes ------------------------------------------------------

def _widths(config):
    c = config
    h, heads, d = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    kv = c["num_key_value_heads"] * d
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    conv = c["mamba_d_ssm"] + 2 * gn
    d_in = 2 * c["mamba_d_ssm"] + 2 * gn + c["mamba_n_heads"]
    return {
        "attn": h * (heads * d + 2 * kv) + heads * d * h,
        "mamba": (h * d_in + c["mamba_d_conv"] * conv + conv
                  + 3 * c["mamba_n_heads"] + c["mamba_d_ssm"]
                  + c["mamba_d_ssm"] * h),
        "mlp": 3 * h * c["intermediate_size"],
        "norms": 2 * h,
        "embed": c["vocab_size"] * h,
        "layers": c["num_hidden_layers"], "kv_row": kv, "conv": conv,
        "state": c["mamba_n_heads"] * c["mamba_d_state"] * c["mamba_d_head"],
    }


def layer_params(config):
    """430 120 032 at the published widths: attention 31 457 280, the
    Mamba-2 mixer 68 351 072, the SwiGLU 330 301 440, the two norms
    10 240."""
    w = _widths(config)
    return w["attn"] + w["mamba"] + w["mlp"] + w["norms"]


def param_count(config):
    """4 394 354 048 at 4 layers: the layers, the embedding and the untied
    head (1 336 934 400 each), the final norm."""
    w = _widths(config)
    return w["layers"] * layer_params(config) + 2 * w["embed"] \
        + config["hidden_size"]


def kv_bytes_per_token(config, itemsize):
    """A key and a value row of `num_key_value_heads x head_dim` a layer."""
    w = _widths(config)
    return w["layers"] * 2 * w["kv_row"] * itemsize


def state_bytes_per_seq(config, itemsize):
    """(the recurrences' float32 states, the convolutions' last K - 1 inputs
    at `itemsize`) a sequence keeps, whatever its length."""
    w = _widths(config)
    return (w["layers"] * w["state"] * 4,
            w["layers"] * (config["mamba_d_conv"] - 1) * w["conv"] * itemsize)


def decode_least(config, ctx_tokens, batch, itemsize):
    """(flops, bytes) one decode step cannot do without. Bytes: every weight
    once (of the embedding a row per sequence, the head whole), every live
    token's key and value rows once a layer, every sequence's recurrence
    states and convolution tails read and written. Flops: 2 per parameter
    per sequence, scores and values per query head and live token, 5 per
    state value (decay, the outer product's multiply and add, C's multiply
    and add)."""
    c, w = config, _widths(config)
    h = c["hidden_size"]
    weights = w["layers"] * layer_params(c) + w["embed"] + h
    ssm, conv = state_bytes_per_seq(c, itemsize)
    attn_flops, attn_bytes = attend_least(c, ctx_tokens, itemsize)
    nbytes = (weights * itemsize + batch * h * itemsize + attn_bytes
              + 2 * batch * (ssm + conv))
    flops = (2 * weights * batch + attn_flops
             + 5 * batch * w["layers"] * w["state"])
    return flops, nbytes


def attend_least(config, ctx_tokens, itemsize):
    """(flops, bytes) of one decode step's paged attention launches alone:
    every live token's key and value rows once a layer; scores and values
    per query head and live token."""
    c, w = config, _widths(config)
    flops = (2 * w["layers"] * c["num_attention_heads"] * ctx_tokens
             * 2 * c["head_dim"])
    return flops, kv_bytes_per_token(c, itemsize) * ctx_tokens


def state_update_least(config, batch):
    """(flops, bytes) of ONE launch of the decode state update (a layer's
    states of `batch` sequences): the float32 state read and written, x,
    dt, B and C read and y written, float32."""
    c, w = config, _widths(config)
    heads, p = c["mamba_n_heads"], c["mamba_d_head"]
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    nbytes = batch * (2 * w["state"] + 2 * heads * p + heads + 2 * gn) * 4
    return 5 * batch * w["state"], nbytes


def _itemsize(run):
    return {"float32": 4, "bfloat16": 2}[run.config["serve"]["weight_dtype"]]


def least_decode(run, n_events):
    """(flops, bytes) of `n_events` decode programs at the window's median
    live context."""
    ctx = run.samples.get("step_ctx_tokens")
    if not ctx:
        return None
    flops, nbytes = decode_least(run.config, statistics.median(ctx),
                                 run.config["serve"]["max_batch"],
                                 _itemsize(run))
    return n_events * flops, n_events * nbytes


def _decode_steps(run):
    """Decode programs in the trace: the most frequent `jit__unknown`, found
    as `decode_mfu.falconh1` finds it."""
    tr = run.reduced_trace()
    if tr is None:
        return 0
    return len(tr.module_events("jit__unknown", min(tr.devices), True))


def least_attend(run, n_events):
    """(flops, bytes) of the trace's paged attention launches, as many
    steps' as the trace holds decode programs (the prefills are over before
    the window opens), at the window's median live context."""
    ctx, steps = run.samples.get("step_ctx_tokens"), _decode_steps(run)
    if not ctx or not steps:
        return None
    flops, nbytes = attend_least(run.config, statistics.median(ctx),
                                 _itemsize(run))
    return steps * flops, steps * nbytes


def least_state(run, n_events):
    """(flops, bytes) of `n_events` launches of the state update."""
    flops, nbytes = state_update_least(run.config,
                                       run.config["serve"]["max_batch"])
    return n_events * flops, n_events * nbytes


# -- the system under test ---------------------------------------------------

def model_config(config):
    from paddle_tpu.text.models.falcon_h1 import FalconH1Config

    return FalconH1Config(dtype=config["serve"]["weight_dtype"],
                          **{k: config[k] for k in PUBLISHED})


def build_engine(config, seed):
    """LLMEngine(model.eval()) with the serving settings the file states;
    weights drawn on the device from the seed."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import LLMEngine
    from paddle_tpu.text.models.falcon_h1 import FalconH1ForCausalLM

    s = config["serve"]
    cfg = model_config(config)
    paddle.seed(int(seed) % 2147483647)
    model = FalconH1ForCausalLM(cfg)
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


def _softplus(x):
    import jax.numpy as jnp

    return jnp.logaddexp(x, 0.0)


def reference_hidden(params, ids, cfg, q_block=256, ffn_block=5376):
    """The forward pass up to the final norm, in plain float32 jax.numpy at
    `highest` matmul precision, from the equations of the module's
    docstring. `params` is the engine's own tree (text/models/falcon_h1.py:
    a list of layers, each its own tree), cast up a matrix at a time, the
    SwiGLU `ffn_block` of its columns at a time; attention runs over the
    whole sequence in blocks of `q_block` queries, the mask built from
    positions; the recurrence one position at a time. ids [S] -> N(x)
    [S, hidden]."""
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = cfg.rms_norm_eps
    heads, kv_heads, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.head_dim)
    s_len, hid = ids.shape[0], cfg.hidden_size
    q_block = min(q_block, s_len)
    if s_len % q_block:
        raise ValueError(f"{s_len} positions in query blocks of {q_block}")
    pos = jnp.arange(s_len)
    d_ssm, gn = cfg.mamba_d_ssm, cfg.mamba_n_groups * cfg.mamba_d_state
    ssm_heads, p_head = cfg.mamba_n_heads, cfg.mamba_d_head
    per_group = ssm_heads // cfg.mamba_n_groups

    def norm(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * _f32(w)

    inv = jnp.asarray([cfg.rope_theta ** (-2.0 * i / d)
                       for i in range(d // 2)], jnp.float32)

    def rotary(x):
        """R_t over the last dim of x [S, heads, d], pairing dim i with
        i + d/2."""
        ang = (pos.astype(jnp.float32)[:, None] * inv)[:, None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], -1)

    def attention(u, ap):
        qkv = (u * cfg.attention_in_multiplier) @ _f32(ap["wqkv"])
        q = qkv[:, :heads * d].reshape(s_len, heads, d)
        k = qkv[:, heads * d:(heads + kv_heads) * d].reshape(
            s_len, kv_heads, d) * cfg.key_multiplier
        v = qkv[:, (heads + kv_heads) * d:].reshape(s_len, kv_heads, d)
        q, k = rotary(q), rotary(k)
        # query head h reads K/V head h // G
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i, q_block)
            scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
            seen = (i + jnp.arange(q_block))[:, None] >= pos[None, :]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        out = jax.lax.map(block, jnp.arange(0, s_len, q_block))
        return (out.reshape(s_len, heads * d) @ _f32(ap["wo"])) \
            * cfg.attention_out_multiplier

    def mamba(u, mp):
        proj = (u * cfg.ssm_in_multiplier) @ _f32(mp["w_in"])
        edges = np.cumsum([0, d_ssm, d_ssm, gn, gn, ssm_heads])
        z, x, b, c, dt = (proj[:, lo:hi] * m for lo, hi, m in zip(
            edges[:-1], edges[1:], cfg.ssm_multipliers))
        xbc = jnp.concatenate([x, b, c], -1)
        taps = _f32(mp["taps"])                       # [K, channels]
        k_taps = taps.shape[0]
        padded = jnp.pad(xbc, ((k_taps - 1, 0), (0, 0)))
        conv = _f32(mp["conv_b"]) + sum(
            taps[j] * padded[j:j + s_len] for j in range(k_taps))
        xbc = _silu(conv)
        x = xbc[:, :d_ssm].reshape(s_len, ssm_heads, p_head)
        b = xbc[:, d_ssm:d_ssm + gn].reshape(s_len, cfg.mamba_n_groups, -1)
        c = xbc[:, d_ssm + gn:].reshape(s_len, cfg.mamba_n_groups, -1)
        dt = _softplus(dt + _f32(mp["dt_bias"]))       # [S, heads]
        a = -jnp.exp(_f32(mp["A_log"]))
        group = jnp.arange(ssm_heads) // per_group

        def step(state, xs):
            """S [heads, d_head, d_state]: one position."""
            x_t, dt_t, b_t, c_t = xs
            state = jnp.exp(dt_t * a)[:, None, None] * state \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :]
            return state, jnp.einsum("hpn,hn->hp", state, c_t[group])

        _, y = jax.lax.scan(
            step, jnp.zeros((ssm_heads, p_head, cfg.mamba_d_state),
                            jnp.float32), (x, dt, b, c))
        y = (y + _f32(mp["D"])[:, None] * x).reshape(s_len, d_ssm)
        g = (y * _silu(z)).reshape(s_len, cfg.mamba_n_groups, -1)
        g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
        y = g.reshape(s_len, d_ssm) * _f32(mp["norm"])
        return (y @ _f32(mp["w_out"])) * cfg.ssm_out_multiplier

    def ffn(u, fp):
        width = cfg.intermediate_size
        block = math.gcd(width, ffn_block)
        m_g, m_d = cfg.mlp_multipliers

        def one(acc, j):
            wg = jax.lax.dynamic_slice_in_dim(fp["w13"], j, block, axis=1)
            wu = jax.lax.dynamic_slice_in_dim(fp["w13"], width + j, block,
                                              axis=1)
            wd = jax.lax.dynamic_slice_in_dim(fp["w2"], j, block)
            act = _silu((u @ _f32(wg)) * m_g) * (u @ _f32(wu))
            return acc + act @ _f32(wd), None

        out, _ = jax.lax.scan(one, jnp.zeros((s_len, hid), jnp.float32),
                              jnp.arange(0, width, block))
        return out * m_d

    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], ids, axis=0)) \
            * cfg.embedding_multiplier
        for lp in params["layers"]:
            u = norm(x, lp["ln_in"])
            x = x + attention(u, lp["attn"]) + mamba(u, lp["mamba"])
            x = x + ffn(norm(x, lp["ln_ff"]), lp["mlp"])
        return norm(x, params["norm_f"])


def reference_logits(params, ids, cfg, start=0, n_rows=None, q_block=256):
    """`reference_hidden` through the untied head: ids [S] -> logits
    [n_rows, V] of rows `start : start + n_rows` (all by default)."""
    import jax

    x = reference_hidden(params, ids, cfg, q_block)
    rows = x if n_rows is None else jax.lax.dynamic_slice_in_dim(
        x, start, n_rows)
    with jax.default_matmul_precision("highest"):
        return (rows @ _f32(params["head"])) * cfg.lm_head_multiplier


def teacher_forced_deficits(params, n_head, prompt, output, pad_to,
                            cfg=None, limits=None, row_bucket=256):
    """For every emitted token, how far its reference logit lies under the
    reference's largest logit at that position, the emitted sequence fed as
    input (zero-padded to `pad_to`; causal, so the padding changes
    nothing). Only the emitted
    rows, in a window of a whole number of `row_bucket` rows, meet the head,
    a slice of the vocabulary at a time.

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
    print(f"[tpubench] falcon_h1 reference: {len(prompt)} prompt + "
          f"{len(output)} emitted tokens, largest token deficit "
          f"{d.max():.5f}, the request's mean {d.mean():.5f}", flush=True)
    scale = float(limits["logit_margin"]) / float(limits["logit_mean_margin"])
    return np.append(d, d.mean() * scale)


@functools.lru_cache(maxsize=None)
def _deficits_fn(cfg, n_rows, vocab_block=8192):
    """Compiled once per model configuration and row window. The head meets the rows `vocab_block` columns at a
    time, a running largest logit and the emitted token's logit taken from
    the block that holds it (261 120 x 5120 float32 values would not fit
    beside a live engine, and a gather of the head's columns transposes the
    whole head: 2.5 GiB)."""
    import math

    import jax
    import jax.numpy as jnp

    def deficits(params, ids, start, picked_ids):
        x = reference_hidden(params, ids, cfg)
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_rows)
        head = params["head"]
        width = math.gcd(head.shape[1], vocab_block)

        def block(carry, c):
            largest, picked = carry
            with jax.default_matmul_precision("highest"):
                logits = rows @ _f32(jax.lax.dynamic_slice_in_dim(
                    head, c, width, axis=1))
            at = picked_ids - c
            inside = (at >= 0) & (at < width)
            here = jnp.take_along_axis(
                logits, jnp.clip(at, 0, width - 1)[:, None], axis=1)[:, 0]
            return (jnp.maximum(largest, logits.max(-1)),
                    jnp.where(inside, here, picked)), None

        (largest, picked), _ = jax.lax.scan(
            block, (jnp.full((n_rows,), -jnp.inf), jnp.zeros((n_rows,))),
            jnp.arange(0, head.shape[1], width))
        return (largest - picked) * cfg.lm_head_multiplier

    return jax.jit(deficits)
