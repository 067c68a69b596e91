"""Falcon-H1 (`model_type` `falcon_h1`): in every layer a grouped-query
attention and a Mamba-2 state-space mixer side by side on the same
normalised input, their outputs added to the residual together, then
a SwiGLU; every branch scaled by the published muP multipliers.

Published description: huggingface.co/tiiuae/Falcon-H1-34B-Instruct
`config.json`. `N` being RMSNorm (weight, no bias, eps
`rms_norm_eps`) and no projection having a bias:

    x_0 = E[ids] * embedding_multiplier
    u   = N_in(x)
    x'  = x + Attn(u * attention_in_multiplier) * attention_out_multiplier
            + Mamba(u * ssm_in_multiplier) * ssm_out_multiplier
    x'' = x' + W_d (silu(N_ff(x') W_g * m_g) * (N_ff(x') W_u)) * m_d
    logits = N_f(x) W_head * lm_head_multiplier     (the head untied)

with `(m_g, m_d)` = `mlp_multipliers`.

- `Attn`: `num_attention_heads` query heads over `num_key_value_heads`
  K/V heads of `head_dim` (query head `h` reads K/V head `h // G`), the
  keys times `key_multiplier`, rotary over the whole head (theta
  `rope_theta`, the default rule), scores `/ sqrt(head_dim)`, causal
  softmax, `W_o`.
- `Mamba` (Mamba-2, `mamba_n_heads` heads of `mamba_d_head` over
  `mamba_n_groups` groups of `mamba_d_state`): `[z | xBC | dt] = (u'
  W_in) * mup`, `mup` the five `ssm_multipliers` over the five parts
  in that order (z, x, B, C, dt); `xBC = silu(conv(xBC) + b)`, a causal
  depthwise convolution of `mamba_d_conv` taps; `x | B | C` split from
  it; `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`; the recurrence
  of `incubate/nn/ssm.py` (head `h` reads group `h // (heads /
  groups)`); `y = y + D x`; `y = N_g(y * silu(z))`, an RMSNorm over
  each of the `mamba_n_groups` groups of channels (`mamba_norm_before_
  gate` false); `W_out`.

The layers are UNROLLED, each its own tree in `params["layers"]`;
what a layer does with the cache is the calling program's: `layers`
is handed `attend`, `window` and `scan` (`inference/serving/
state_runner.py` has their contracts). What a sequence keeps of a
layer is its K/V rows (paged), the convolution's last `mamba_d_conv
- 1` inputs and the recurrence's float32 state `[heads, d_state,
d_head]` (per slot, `slot_state`).

Assumed where the config is silent: half-split rotary pairing; the
in-projection's split order as listed; how the weights are drawn
(`draw_stds`: with the published multipliers applied, each branch
adds about 1 a channel to the residual, scores spread by 2 and
logits by 3; `A_log`, `dt_bias`, `D`
as Mamba-2 initialises them). `A_log`, `dt_bias` and `D` stay
float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ...core.engine import apply_op
from ...incubate.nn.ssm import ssd_chunked
from ...nn.layer.layers import Layer
from .common import SeededTree, _times, attend_dense, rms_norm, rotate

__all__ = ["FalconH1Config", "FalconH1Model", "FalconH1ForCausalLM",
           "draw_stds"]


@dataclass(frozen=True)
class FalconH1Config:
    """Published key names; `num_layers`, `num_heads` and `max_seq_len`
    beside them are the names the serving engine reads of any model."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_head: int = 128
    mamba_d_ssm: int = 4096
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_n_heads: int = 32
    mamba_norm_before_gate: bool = False
    mamba_rms_norm: bool = True
    dtype: str = "float32"

    def __post_init__(self):
        for name in ("mlp_multipliers", "ssm_multipliers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        # published as the integer 1e11
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        if self.tie_word_embeddings or self.mamba_norm_before_gate \
                or not (self.mamba_rms_norm and self.mamba_conv_bias):
            raise ValueError(
                "falcon_h1 as published: an untied head, the gated norm "
                "after the gate, a convolution bias")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2 \
                or self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm \
                or self.mamba_n_heads % self.mamba_n_groups \
                or len(self.ssm_multipliers) != 5 \
                or len(self.mlp_multipliers) != 2:
            raise ValueError(
                f"{self.num_attention_heads} query heads over "
                f"{self.num_key_value_heads} K/V heads of {self.head_dim}; "
                f"{self.mamba_n_heads} SSM heads of {self.mamba_d_head} "
                f"over {self.mamba_n_groups} groups, d_ssm "
                f"{self.mamba_d_ssm}")

    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def num_heads(self):
        return self.num_attention_heads

    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def kv_row(self):
        """Values one token's keys (or values) are, per attention."""
        return self.num_key_value_heads * self.head_dim

    @property
    def conv_dim(self):
        """Channels of the convolution: x, B and C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_parts(self):
        """Widths of z, x, B, C, dt in the in-projection, in order."""
        gn = self.mamba_n_groups * self.mamba_d_state
        return (self.mamba_d_ssm, self.mamba_d_ssm, gn, gn,
                self.mamba_n_heads)


# how the weights are drawn (not published; `draw_stds`): attention
# scores spread by 2.0, logits by 3.0; four taps at 0.5 keep the
# variance of the convolution's input, its bias at 0.1
SCORE_STD, LOGIT_STD, TAPS_STD, CONV_BIAS_STD = 2.0, 3.0, 0.5, 0.1


def draw_stds(cfg):
    """The standard deviation each matrix is drawn at: with the
    published multiplier after it (and an input of RMS 1), a
    projection's output has RMS 1 (the attention's q and k sqrt of
    `SCORE_STD`, so that scores spread by it; the head's logits spread
    by `LOGIT_STD`); the embedding rows, times their
    multiplier, RMS 1. Column groups of one matrix get their own
    (`[z | x | B | C | dt]`, `[q | k | v]`, `[gate | up]`)."""
    c = cfg
    h = c.hidden_size
    r = 1.0 / math.sqrt(h)
    sharp = math.sqrt(SCORE_STD)
    q_in = r / c.attention_in_multiplier
    m_in = r / c.ssm_in_multiplier
    return {
        "embed": 1.0 / c.embedding_multiplier,
        "wqkv": (sharp * q_in, sharp * q_in / c.key_multiplier, q_in),
        "wo": 1.0 / (math.sqrt(c.num_attention_heads * c.head_dim)
                     * c.attention_out_multiplier),
        "w_in": tuple(m_in / m for m in c.ssm_multipliers),
        "w_out": 1.0 / (math.sqrt(c.mamba_d_ssm) * c.ssm_out_multiplier),
        "w13": (r / c.mlp_multipliers[0], r),
        "w2": 1.0 / (math.sqrt(c.intermediate_size) * c.mlp_multipliers[1]),
        "head": LOGIT_STD * r / c.lm_head_multiplier,
    }


# -- the block (pure jnp; the serving runner reads `layers`) ---------------

def attention_operator(u, carry, ap, a, attend, positions, cfg):
    """Grouped-query attention over tokens u [T, H] at `positions`
    [T], keys times `key_multiplier`, rotated; the calling program
    attends and keeps the rows."""
    t, d = u.shape[0], cfg.head_dim
    hq, row = cfg.num_attention_heads, cfg.kv_row
    q, k, v = jnp.split(_times(u, cfg.attention_in_multiplier) @ ap["wqkv"],
                        [hq * d, hq * d + row], axis=-1)
    k = _times(k, cfg.key_multiplier)
    q = rotate(q.reshape(t, hq, d), positions[:, None], cfg.rope_theta)
    k = rotate(k.reshape(t, -1, d), positions[:, None],
               cfg.rope_theta).reshape(t, row)
    with jax.named_scope("gqa/attend"):
        out, carry = attend(q, k, v, carry, a)
    return _times(out @ ap["wo"], cfg.attention_out_multiplier), carry


def gated_group_norm(y, z, w, groups, eps):
    """RMSNorm over each of `groups` groups of channels of `y *
    silu(z)`, float32, times the gain."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(g.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g.reshape(y.shape) * w.astype(jnp.float32)


def mamba_operator(u, carry, mp, s, window, scan, cfg):
    """The Mamba-2 mixer over tokens u [T, H]; the calling program
    says what precedes each token (`window`: the convolution's
    inputs) and runs the recurrence (`scan`: the state)."""
    t = u.shape[0]
    heads, p = cfg.mamba_n_heads, cfg.mamba_d_head
    groups, n = cfg.mamba_n_groups, cfg.mamba_d_state
    with jax.named_scope("mamba/in_proj"):
        proj = _times(u, cfg.ssm_in_multiplier) @ mp["w_in"]
        mup = np.concatenate([np.full((w,), m, np.float32) for w, m in zip(
            cfg.in_proj_parts, cfg.ssm_multipliers)])
        proj = proj * jnp.asarray(mup, proj.dtype)
        z, xbc, dt = jnp.split(
            proj, [cfg.mamba_d_ssm, cfg.mamba_d_ssm + cfg.conv_dim], -1)
    with jax.named_scope("mamba/conv"):
        win, carry = window(xbc, carry, s)                # [T, K, conv]
        conv = (win.astype(jnp.float32) * mp["taps"].astype(jnp.float32)
                ).sum(1) + mp["conv_b"].astype(jnp.float32)
        xbc = jax.nn.silu(conv).astype(u.dtype)
        x, b, c = jnp.split(xbc, [cfg.mamba_d_ssm,
                                  cfg.mamba_d_ssm + groups * n], -1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + mp["dt_bias"])
    x = x.reshape(t, heads, p)
    with jax.named_scope("mamba"):
        y, carry = scan(x, dt, -jnp.exp(mp["A_log"]), b.reshape(t, groups, n),
                        c.reshape(t, groups, n), carry, s)
    y = y + mp["D"][:, None] * x.astype(jnp.float32)
    with jax.named_scope("mamba/gate_norm"):
        y = gated_group_norm(y.reshape(t, -1), z, mp["norm"], groups,
                             cfg.rms_norm_eps).astype(u.dtype)
    with jax.named_scope("mamba/out_proj"):
        return _times(y @ mp["w_out"], cfg.ssm_out_multiplier), carry


def mlp(u, fp, cfg):
    gate, up = jnp.split(u @ fp["w13"], 2, axis=-1)
    m_g, m_d = cfg.mlp_multipliers
    return _times((jax.nn.silu(_times(gate, m_g)) * up) @ fp["w2"], m_d)


def layers(params, x, carry, attend, window, scan, positions, live, cfg):
    """Every layer over `x [T, hidden]`, unrolled, with the calling
    program's `attend`, `window` and `scan` (module docstring; layer
    `l` is attention `l`, windowed layer `l` and state-space layer
    `l`). Returns (x, carry, None: no rows for the runner to write,
    {}: no routing to count)."""
    eps = cfg.rms_norm_eps
    for i, lp in enumerate(params["layers"]):
        u = rms_norm(x, lp["ln_in"], eps)
        a, carry = attention_operator(u, carry, lp["attn"], i, attend,
                                      positions, cfg)
        m, carry = mamba_operator(u, carry, lp["mamba"], i, window, scan,
                                  cfg)
        x = x + a + m
        x = x + mlp(rms_norm(x, lp["ln_ff"], eps), lp["mlp"], cfg)
    return x, carry, None, {}


def logits(params, x, cfg):
    """Final norm and the untied head times `lm_head_multiplier`,
    float32."""
    x = rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
    return jnp.dot(x, params["head"], preferred_element_type=jnp.float32) \
        * cfg.lm_head_multiplier


def embed(params, ids, cfg):
    return _times(jnp.take(params["embed"], ids, axis=0),
                   cfg.embedding_multiplier)


def _k_forward(ids, params, cfg):
    """Full causal forward, ids [B, S] -> logits [B, S, V] float32:
    what training and the tests run, through `layers`. Attention, the
    convolution and the recurrence run a sequence at a time (the
    recurrence chunked, from a zero state); the projections see all
    B x S tokens as one list."""
    b, s = ids.shape
    n = cfg.mamba_d_conv - 1

    def per_seq(x):
        return x.reshape((b, s) + x.shape[1:])

    def attend(q, k, v, carry, a):
        out = jax.vmap(attend_dense)(per_seq(q), per_seq(k), per_seq(v))
        return out.reshape(b * s, -1), carry

    def window(z, carry, c):
        zp = jnp.pad(per_seq(z), ((0, 0), (n, 0), (0, 0)))
        win = jnp.stack([zp[:, j:j + s] for j in range(n + 1)], 2)
        return win.reshape((b * s,) + win.shape[2:]), carry

    def scan(x, dt, A, B, C, carry, i):
        y, _ = jax.vmap(lambda *a: ssd_chunked(*a[:2], A, *a[2:]))(
            per_seq(x), per_seq(dt), per_seq(B), per_seq(C))
        return y.reshape((b * s,) + y.shape[2:]), carry

    x = embed(params, ids.reshape(b * s), cfg)
    x, _, _, _ = layers(params, x, (), attend, window, scan,
                        jnp.tile(jnp.arange(s), b), None, cfg)
    return logits(params, x, cfg).reshape(b, s, -1)


# -- the Layer ---------------------------------------------------------------

class FalconH1Model(SeededTree):
    """Decoder of `num_hidden_layers` layers, each its own tree."""

    # what the serving runner reads (state_runner.StateRunner)
    decoder_layers = staticmethod(layers)
    attend_dense = staticmethod(attend_dense)
    logits = staticmethod(logits)
    embed = staticmethod(embed)
    routed_experts = None        # no experts

    def __init__(self, config: FalconH1Config):
        super().__init__(config)
        c = config
        h, d = c.hidden_size, c.head_dim
        hq, heads = c.num_attention_heads, c.mamba_n_heads
        std = draw_stds(c)

        def layer():
            return {
                "ln_in": self._ones("ln_in", (h,)),
                "ln_ff": self._ones("ln_ff", (h,)),
                "attn": {
                    "wqkv": self._columns(
                        "wqkv", h, (hq * d, c.kv_row, c.kv_row),
                        std["wqkv"]),
                    "wo": self._normal("wo", (hq * d, h), layered=False,
                                       std=std["wo"])},
                "mamba": {
                    "w_in": self._columns("w_in", h, c.in_proj_parts,
                                          std["w_in"]),
                    # taps stored [K, channels] (lanes on the channels),
                    # the published [channels, 1, K] transposed
                    "taps": self._normal("taps", (c.mamba_d_conv, c.conv_dim),
                                         layered=False, std=TAPS_STD),
                    "conv_b": self._normal("conv_b", (c.conv_dim,),
                                           layered=False,
                                           std=CONV_BIAS_STD),
                    **self._ssm_constants(heads),
                    "norm": self._ones("norm", (c.mamba_d_ssm,)),
                    "w_out": self._normal("w_out", (c.mamba_d_ssm, h),
                                          layered=False, std=std["w_out"])},
                "mlp": {
                    "w13": self._columns("w13", h, (c.intermediate_size,) * 2,
                                         std["w13"]),
                    "w2": self._normal("w2", (c.intermediate_size, h),
                                       layered=False, std=std["w2"])}}

        self._tree = {
            "embed": self._normal("embed", (c.vocab_size, h), layered=False,
                                  std=std["embed"]),
            "norm_f": self._ones("norm_f", (h,)),
            "layers": [layer() for _ in range(c.num_hidden_layers)],
            "head": self._normal("head", (h, c.vocab_size), layered=False,
                                 std=std["head"]),
        }

    def _columns(self, name, rows, widths, stds):
        """A `[rows, sum(widths)]` matrix whose column groups are drawn
        at their own standard deviations."""
        key = jax.random.fold_in(self._key, self._n_leaf)
        scale = np.concatenate([np.full((w,), s, np.float32)
                                for w, s in zip(widths, stds)])
        value = jax.jit(lambda k: (jax.random.normal(
            k, (rows, scale.size), jnp.float32) * scale).astype(self._dtype))(
                key)
        return self._add(name, value)

    def _ssm_constants(self, heads):
        """Mamba-2's initialisation, float32: A uniform in [1, 16]
        (`A_log = log A`), dt_bias the inverse softplus of a dt
        log-uniform in [0.001, 0.1], D = 1."""
        key = jax.random.fold_in(self._key, self._n_leaf)
        ka, kd = jax.random.split(key)
        a = jax.random.uniform(ka, (heads,), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(kd, (heads,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {"A_log": self._add("A_log", jnp.log(a)),
                "dt_bias": self._add("dt_bias",
                                     dt + jnp.log(-jnp.expm1(-dt))),
                "D": self._add("D", jnp.ones((heads,), jnp.float32))}

    @property
    def n_attentions(self):
        return self.config.num_hidden_layers

    @property
    def kv_heads(self):
        """(query heads, K/V heads, head_dim)."""
        c = self.config
        return c.num_attention_heads, c.num_key_value_heads, c.head_dim

    @property
    def ssm_heads(self):
        """(heads, groups, d_state, d_head) of the recurrence."""
        c = self.config
        return (c.mamba_n_heads, c.mamba_n_groups, c.mamba_d_state,
                c.mamba_d_head)

    @property
    def slot_state(self):
        """What a sequence keeps beside its K/V rows, as `(kind,
        (layers, *shape a layer), dtype)`: the convolution's last K - 1
        inputs (the cache's dtype), and the recurrence's state
        `[heads, d_state, d_head]` in float32 (a running sum over
        thousands of steps with decays near 1)."""
        c = self.config
        layers_ = c.num_hidden_layers
        return (("window", (layers_, c.mamba_d_conv - 1, c.conv_dim), None),
                ("ssm", (layers_, c.mamba_n_heads, c.mamba_d_state,
                         c.mamba_d_head), "float32"))

    def forward(self, input_ids):
        return apply_op("falcon_h1_forward", _k_forward, input_ids,
                        self._tree, cfg=self.config)


class FalconH1ForCausalLM(Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.model = FalconH1Model(config)
        self.config = config

    def forward(self, input_ids):
        return self.model(input_ids)
