"""GPT-2 (Radford et al. 2019; the Megatron-LM 345M shape): how the benchmark
builds the system under test from a configuration file, the operations and
bytes its work needs (computed from shapes, never read from the program), and
its plain float32 reference.
"""
from __future__ import annotations

import math
import statistics

WIDTH_KEYS = {"vocab_size": "vocab_size", "n_embd": "hidden_size",
              "n_layer": "num_layers", "n_head": "num_heads",
              "n_inner": "ffn_hidden", "n_positions": "max_seq_len"}


def width(config):
    """The program's GPTConfig keywords from the published key names."""
    return {ours: int(config[theirs]) for theirs, ours in WIDTH_KEYS.items()}


# -- counts from shapes ------------------------------------------------------

def param_count(config):
    """Parameters with the tied head counted once: 354 871 296 at 345M."""
    w = width(config)
    h, f, n, v, s = (w["hidden_size"], w["ffn_hidden"], w["num_layers"],
                     w["vocab_size"], w["max_seq_len"])
    layer = (h * 3 * h + 3 * h) + (h * h + h) + (h * f + f) + (f * h + h) \
        + 4 * h
    return v * h + s * h + n * layer + 2 * h


def train_flops_per_token(config, seq):
    """Forward plus backward, nothing recomputed: 6 per parameter (wpe is
    looked up, not multiplied, but is 0.3 % of N and kept for the usual 6N),
    plus causal attention: per layer two matmuls (QK^T, PV) of 2*hidden flops
    per key seen, seq/2 keys on average, three times for the backward."""
    w = width(config)
    attn = w["num_layers"] * 3 * 2 * 2 * (seq / 2) * w["hidden_size"]
    return 6 * param_count(config) + attn


def flash_flops_per_step(config, rows, seq):
    """What the flash kernels of one train step have to compute: the causal
    half of 2 forward and 4 backward matmuls (dV, dP, dQ, dK) per layer. The
    backward kernels' recomputation of QK^T is not counted."""
    w = width(config)
    head = w["hidden_size"] // w["num_heads"]
    one = 2 * rows * w["num_heads"] * (seq * seq / 2) * head
    return w["num_layers"] * 6 * one


def flash_bytes_per_step(config, rows, seq, itemsize=2):
    """q, k, v, o read or written once forward; q, k, v, o, do read and
    dq, dk, dv written backward."""
    w = width(config)
    return w["num_layers"] * 12 * rows * seq * w["hidden_size"] * itemsize


def weight_bytes(config, itemsize):
    return param_count(config) * itemsize


def kv_bytes_per_token(config, itemsize):
    w = width(config)
    return 2 * w["num_layers"] * w["hidden_size"] * itemsize


def decode_least(config, ctx_tokens, batch, itemsize):
    """(flops, bytes) one decode step cannot do without: every weight read
    once and every live key and value read once; 2 flops per parameter per
    sequence plus the attention over the live context."""
    w = width(config)
    flops = 2 * param_count(config) * batch \
        + 2 * 2 * w["num_layers"] * w["hidden_size"] * ctx_tokens
    nbytes = weight_bytes(config, itemsize) \
        + kv_bytes_per_token(config, itemsize) * ctx_tokens
    return flops, nbytes


def least_flash_train(run, n_events):
    """(flops, bytes) of `n_events` traced flash kernel calls of whole train
    steps: 3 calls per layer per step (forward, dq, dkv), each chip on its own
    rows."""
    steps = n_events / (3 * int(run.config["n_layer"]))
    rows, seq = run.values["rows_per_chip"], run.values["sequence_length"]
    return (steps * flash_flops_per_step(run.config, rows, seq),
            steps * flash_bytes_per_step(run.config, rows, seq))


def least_decode(run, n_events):
    """(flops, bytes) of `n_events` decode programs at the serving
    configuration's types, the live context being the median over the
    window's steps."""
    ctx = run.samples.get("step_ctx_tokens")
    if not ctx:
        return None
    s = run.config["serve"]
    itemsize = {"float32": 4, "bfloat16": 2}[s["weight_dtype"]]
    flops, nbytes = decode_least(run.config, statistics.median(ctx),
                                 s["max_batch"], itemsize)
    return n_events * flops, n_events * nbytes


# -- the system under test ---------------------------------------------------

def _model_seed(seed):
    return int(seed) % 2147483647


def build_train_model(config, seed, scan_unroll=None, flash=True):
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    t = config["train"]
    paddle.seed(_model_seed(seed))
    cfg = GPTConfig(dropout=0.0, remat=bool(t["remat"]),
                    use_flash_attention=flash,
                    scan_unroll=t["scan_unroll"] if scan_unroll is None
                    else scan_unroll, **width(config))
    model = GPTForCausalLM(cfg)
    if t["amp_level"] != "O0":
        model = amp.decorate(model, level=t["amp_level"], dtype=t["dtype"])
    return model


def build_train_step(config, model, chips):
    """The compiled train step a user would build: TrainStepCompiler on one
    chip, DistributedTrainStepCompiler over a dp mesh on several."""
    import jax
    import paddle_tpu.optimizer as optim

    o = config["train"]["optimizer"]
    opt = getattr(optim, o["name"])(
        learning_rate=o["learning_rate"], parameters=model.parameters(),
        weight_decay=o["weight_decay"], multi_precision=o["multi_precision"])
    if chips == 1:
        from paddle_tpu.jit import TrainStepCompiler

        return TrainStepCompiler(model, opt, loss_fn=None)
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.jit.distributed import DistributedTrainStepCompiler

    mesh = build_mesh({"dp": chips}, devices=jax.devices()[:chips])
    return DistributedTrainStepCompiler(model, opt, loss_fn=None, mesh=mesh)


def forward_loss(model, ids, rows):
    """Forward-only loss, `rows` sequences at a time (equal chunks, so the
    mean of the chunk means is the batch mean)."""
    import paddle_tpu as paddle

    with paddle.no_grad():
        parts = [float(model(paddle.to_tensor(ids[i:i + rows]),
                             paddle.to_tensor(ids[i:i + rows])).item())
                 for i in range(0, ids.shape[0], rows)]
    return sum(parts) / len(parts)


def build_engine(config, seed):
    """LLMEngine(model.eval()) with the serving settings the file states."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import LLMEngine
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    s = config["serve"]
    paddle.seed(_model_seed(seed))
    model = GPTForCausalLM(GPTConfig(dropout=0.0, use_flash_attention=True,
                                     **width(config)))
    model.eval()
    return LLMEngine(model, max_batch=s["max_batch"],
                     block_size=s["block_size"],
                     num_blocks=s.get("num_blocks"), dtype=s["kv_dtype"],
                     spec_k=s["spec_k"], prefix_cache=s["prefix_cache"])


# -- the plain reference -----------------------------------------------------

def reference_logits(params, ids, n_head, eps=1e-5):
    """GPT-2's forward pass in plain float32 jax.numpy: learned positions,
    pre-LayerNorm blocks, dense causal attention, tanh GELU, tied head. No
    kernel, no cache, no batching. `params` is the tree of
    text/models/gpt.py (wte, wpe, blocks with a leading layer axis, lnf);
    ids is [S]; returns logits [S, V]."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)          # noqa: E731

    def ln(x, w, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * f32(w) + f32(b)

    with jax.default_matmul_precision("highest"):
        s = ids.shape[0]
        x = f32(params["wte"])[ids] + f32(params["wpe"])[:s]
        hidden = x.shape[-1]
        d = hidden // n_head
        mask = jnp.tril(jnp.ones((s, s), bool))

        def block(x, bp):
            h = ln(x, bp["ln1_w"], bp["ln1_b"])
            q, k, v = jnp.split(h @ f32(bp["qkv_w"]) + f32(bp["qkv_b"]), 3,
                                axis=-1)
            q, k, v = (a.reshape(s, n_head, d).transpose(1, 0, 2)
                       for a in (q, k, v))
            scores = q @ k.transpose(0, 2, 1) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
            attn = (probs @ v).transpose(1, 0, 2).reshape(s, hidden)
            x = x + attn @ f32(bp["proj_w"]) + f32(bp["proj_b"])
            h = ln(x, bp["ln2_w"], bp["ln2_b"])
            h = jax.nn.gelu(h @ f32(bp["fc1_w"]) + f32(bp["fc1_b"]),
                            approximate=True)
            return x + h @ f32(bp["fc2_w"]) + f32(bp["fc2_b"]), None

        # one layer after another (a scan only so that it compiles once)
        x, _ = jax.lax.scan(block, x, params["blocks"])
        x = ln(x, params["lnf_w"], params["lnf_b"])
        return x @ f32(params["wte"]).T


def teacher_forced_deficits(params, n_head, prompt, output, pad_to):
    """For every emitted token, how far its reference logit lies under the
    reference's largest logit at that position, the emitted sequence fed as
    input (zero-padded to `pad_to`; causal, so the padding changes nothing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seq = list(prompt) + list(output)
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq)] = seq
    logits = jax.jit(reference_logits, static_argnums=(2,))(
        params, jnp.asarray(ids), n_head)
    rows = logits[len(prompt) - 1:len(seq) - 1]
    picked = jnp.take_along_axis(
        rows, jnp.asarray(output, jnp.int32)[:, None], axis=-1)[:, 0]
    return np.asarray(rows.max(-1) - picked)
