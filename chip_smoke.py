#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process, the entry points a user would call, GPT-2 345M at its full
published width (24 layers, hidden 1024, 16 heads x 64, FFN 4096, vocab
50304, context 1024), random weights from a seed:

  train      GPTForCausalLM -> amp.decorate(O2, bf16) -> AdamW(multi_precision)
             -> TrainStepCompiler, batch 4 x 1024, flash attention, one
             compile then steady steps; loss finite and falling; step-0 loss
             agrees with dense attention on the same seeded model.
  serve      LLMEngine(max_batch=8), 8 mixed-length requests x 64 new tokens
             under continuous batching; every request returns its 64 tokens,
             no KV block leaks, pool sized from the device's HBM; the wait
             for the device carries the host's accounting, and a traced pass
             leaves the two clocks a non-empty interval (no program starts
             before its enqueue opened or ends after its wait closed).
  kernels    every shipped Pallas kernel compiled by Mosaic (not interpreted)
             at GPT-2 345M geometry and compared with its in-repo reference.
  multichip  on a host with >= 4 chips: DistributedTrainStepCompiler at dp=4
             and dp=2 x mp=2; parameters on 4 devices, HBM balanced, step-0
             loss equal to the one-chip loss on the same global batch.

Any exception, failed assertion or phase that did not run is a non-zero
exit. With no arguments the script requires a TPU and fails without one.
`--preflight` is the CPU rehearsal of the same control flow at toy width
(kernels through the Pallas interpreter); every line it prints says so and
it never prints the result line.

The numbers printed are single observations for a human reading the log, not
benchmark results. The last line of stdout on success is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
"""
from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import math
import os
import statistics
import sys
import time

SEED = 0
# what the latent-attention (MLA) runner stores of a token: 576 values in 640
LATENT_ROW = 640
# the grouped matmul's check: rows, groups, the stack's groups, the
# first of them, K, N
GROUPED_SHAPE = (1024, 8, 12, 4, 2048, 3072)
TOY_GROUPED_SHAPE = (64, 4, 6, 2, 128, 256)

# GPT-2 345M (bench.py's gpt2_345m / serving configs)
WIDTH = dict(vocab_size=50304, hidden_size=1024, num_layers=24, num_heads=16,
             ffn_hidden=4096, max_seq_len=1024)
TRAIN = dict(batch=4, seq=1024, steps=12)
SERVE = dict(lens=(16, 112, 208, 384, 16, 100, 200, 380), new_tokens=64,
             max_batch=8)

# --preflight: same control flow, toy width, CPU
TOY_WIDTH = dict(vocab_size=512, hidden_size=128, num_layers=2, num_heads=2,
                 ffn_hidden=256, max_seq_len=128)
TOY_TRAIN = dict(batch=4, seq=128, steps=4)
TOY_SERVE = dict(lens=(3, 17, 9, 33, 5, 24, 12, 7), new_tokens=8, max_batch=4)

# flash vs dense, one chip vs four, compiled step vs eager forward: the same
# bf16 forward summed in a different order. bf16 keeps 8 mantissa bits, so one
# rounding is 2^-9 relative; the loss is an f32 mean over >= 4096 token losses
# near ln(50304) = 10.8, whose per-token errors average out. The largest
# difference seen on the v5e was 1.2e-4 (one chip vs dp=4); 0.02 absolute is
# ~1/500 of the loss and still far below a real divergence (a wrongly masked
# or unscaled kernel moves the loss by > 0.1).
LOSS_TOL = 0.02

_PREFLIGHT = False


def say(phase, msg):
    tag = "  [preflight — not a chip run]" if _PREFLIGHT else ""
    print(f"[{phase}] {msg}{tag}", flush=True)


def _version(pkg):
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def _free_device_memory():
    """Drop what the last phase left on the devices before the next one
    sizes itself from free HBM."""
    gc.collect()


def _say_peak_hbm(phase):
    from paddle_tpu.monitor import memory

    st = memory.memory_stats()
    peak = st.get("peak_bytes_in_use", st["peak_bytes"])
    say(phase, f"peak HBM {peak / 2**30:.2f} GiB (source {st['source']})")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _gpt(width, **kw):
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp

    paddle.seed(SEED)
    cfg = GPTConfig(dropout=0.0, remat=False, use_flash_attention=True,
                    **width, **kw)
    return amp.decorate(GPTForCausalLM(cfg), level="O2", dtype="bfloat16")


def _adamw(model):
    import paddle_tpu.optimizer as optim

    return optim.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                       weight_decay=0.01, multi_precision=True)


def _batch(width, batch, seq):
    import numpy as np
    import paddle_tpu as paddle

    rng = np.random.RandomState(SEED)
    ids = rng.randint(0, width["vocab_size"], (batch, seq)).astype(np.int32)
    labels = rng.randint(0, width["vocab_size"],
                         (batch, seq)).astype(np.int32)
    return paddle.to_tensor(ids), paddle.to_tensor(labels)


def _eager_loss(model, ids, labels, rows=None):
    """Forward-only loss of the seeded model, `rows` batch rows at a time
    (equal-sized chunks, so the mean of chunk means is the batch mean)."""
    import paddle_tpu as paddle

    n = ids.shape[0]
    rows = rows or n
    with paddle.no_grad():
        parts = [float(model(ids[i:i + rows], labels[i:i + rows]).item())
                 for i in range(0, n, rows)]
    return sum(parts) / len(parts)


def phase_train(width, batch, seq, steps):
    from paddle_tpu.core.monitor import stat_get
    from paddle_tpu.jit import TrainStepCompiler

    ids, labels = _batch(width, batch, seq)

    # reference, outside the timed steps: the same seeded weights, forward
    # only, with the flash kernel and with dense attention. Scanned (not
    # unrolled) so this check costs seconds of compile, not minutes.
    ref = _gpt(width, scan_unroll=1)
    loss_flash = _eager_loss(ref, ids, labels)
    ref.config.use_flash_attention = False
    loss_dense = _eager_loss(ref, ids, labels)
    del ref
    _free_device_memory()
    say("train", f"step-0 loss forward-only: flash={loss_flash:.5f} "
                 f"dense={loss_dense:.5f} diff={abs(loss_flash - loss_dense):.5f} "
                 f"(tolerance {LOSS_TOL}, bf16)")
    assert abs(loss_flash - loss_dense) <= LOSS_TOL, (loss_flash, loss_dense)

    model = _gpt(width, scan_unroll=width["num_layers"])
    step = TrainStepCompiler(model, _adamw(model), loss_fn=None)
    losses, dts = [], []
    for _ in range(steps + 2):
        t0 = time.perf_counter()
        losses.append(float(step(ids, labels).item()))  # host read = sync
        dts.append(time.perf_counter() - t0)
    # call 0 traces and compiles (and pays the footprint-capture compile);
    # call 1 is dispatched through a second jit cache entry (the state is
    # committed to the device by then) — both are set-up, not steady state
    steady = dts[2:]
    step_s = statistics.median(steady)
    say("train", f"first call {dts[0]:.1f} s (jit/train_step/compile_us="
                 f"{stat_get('jit/train_step/compile_us') / 1e6:.1f} s, "
                 f"mem_capture_us={stat_get('jit/train_step/mem_capture_us') / 1e6:.1f} s), "
                 f"second call {dts[1]:.2f} s")
    say("train", f"{len(steady)} steady steps: median {step_s * 1e3:.1f} ms "
                 f"(min {min(steady) * 1e3:.1f}, max {max(steady) * 1e3:.1f}), "
                 f"{batch * seq / step_s:.0f} tokens/s")
    say("train", f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert all(math.isfinite(v) for v in losses), losses
    assert losses[-1] < losses[0], losses
    assert abs(losses[0] - loss_flash) <= LOSS_TOL, (losses[0], loss_flash)
    _say_peak_hbm("train")
    return {"compile_s": dts[0] + dts[1]}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(width, lens, new_tokens, max_batch):
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import LLMEngine, SamplingParams
    from paddle_tpu.inference.serving.kv_cache import bytes_per_block
    from paddle_tpu.monitor import memory
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(SEED)
    model = GPTForCausalLM(GPTConfig(dropout=0.0, use_flash_attention=True,
                                     **width))
    model.eval()
    rng = np.random.RandomState(SEED)
    prompts = [list(rng.randint(1, width["vocab_size"], n)) for n in lens]
    sampling = SamplingParams(max_new_tokens=new_tokens)

    eng = LLMEngine(model, max_batch=max_batch)
    cache = eng.cache
    per_block = bytes_per_block(width["num_layers"], cache.block_size,
                                width["num_heads"],
                                width["hidden_size"] // width["num_heads"],
                                cache.dtype)
    floor_blocks = (64 << 20) // per_block
    stats = memory.memory_stats()
    say("serve", f"KV pool {cache.num_blocks} blocks x {cache.block_size} "
                 f"tokens ({cache.num_blocks * per_block / 2**30:.2f} GiB, "
                 f"{cache.dtype}); 64 MiB would be {floor_blocks} blocks; "
                 f"memory stats source {stats['source']}")
    if jax.devices()[0].platform != "cpu":
        assert stats["source"] == "pjrt", stats
        assert cache.num_blocks > 10 * floor_blocks, cache.num_blocks

    def one_pass():
        rids = [eng.add_request(p, sampling=sampling) for p in prompts]
        t0 = time.perf_counter()
        while eng.has_unfinished():
            eng.step()
        dt = time.perf_counter() - t0
        gaps = []
        for rid in rids:
            req = eng.get_request(rid)
            assert len(req.output_ids) == new_tokens, (
                rid, len(req.output_ids))
            ts = req.token_times
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        leaks = eng.check_drained()
        assert not leaks, leaks
        outs = [list(eng.get_request(rid).output_ids) for rid in rids]
        return dt, gaps, outs

    # pass 1 compiles one prefill program per padded prompt length and the
    # decode program; pass 2 is the same traffic over the compiled programs
    cold_s, _, outs1 = one_pass()
    warm_s, gaps, outs2 = one_pass()
    assert outs1 == outs2, "greedy decode differs between two passes"
    total = len(prompts) * new_tokens
    say("serve", f"{len(prompts)}/{len(prompts)} requests returned "
                 f"{new_tokens} tokens, check_drained empty, both passes")
    say("serve", f"pass 1 (compiles) {cold_s:.1f} s; pass 2 {warm_s:.2f} s = "
                 f"{total / warm_s:.0f} generated tokens/s, median inter-token "
                 f"gap {statistics.median(gaps) * 1e3:.1f} ms "
                 f"(max {max(gaps) * 1e3:.1f} ms)")
    _say_peak_hbm("serve")
    _check_waits(one_pass)
    return {"compile_s": cold_s - warm_s}


def _check_waits(one_pass):
    """The engine's wait spans on this host: what of the host's accounting
    their ring records carry, and, from one traced pass, the interval the
    two inequalities leave the clocks (tpubench/readers/waits.py)."""
    import tempfile

    import jax
    from paddle_tpu.monitor import flight
    from tpubench import xplane
    from tpubench.readers import waits

    recs = [s for s in flight.spans()
            if s["name"] == flight.SPAN_PREFIX + "serve/decode/wait"]
    have = sorted(set(waits.STARVED).intersection(*(r["ids"] for r in recs)))
    say("serve", f"{len(recs)} wait spans, each with {have}")
    on_chip = jax.devices()[0].platform != "cpu"
    # every wait carries what this host keeps (the v5e host of PR 36, a
    # sandboxed kernel, kept neither file), and nothing ran backwards
    want = {name for name, path in (("runq_us", flight._SCHEDSTAT),
                                    ("pressure_us", flight._PRESSURE))
            if os.path.exists(path)}
    assert recs and set(have) == want, (have, want)
    assert all(r["ids"][k] >= 0 for r in recs for k in have)
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            one_pass()
        finally:
            jax.profiler.stop_trace()
        import glob

        (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        tr = xplane.Trace.from_file(path)
        driver, _ = waits.host_plane(path)
    names = {n for n, *_ in driver}
    assert {flight.SPAN_PREFIX + n for n in (
        "serve/decode/put", "serve/decode/ahead", "serve/decode/wait")} \
        <= names, sorted(names)
    if not on_chip:
        say("serve", "traced pass: the three spans are in the host plane; "
                     "no device plane here")
        return
    skew = waits.clock_skew(tr, driver, "serve/decode/enqueue",
                            "serve/decode/wait", "jit__unknown",
                            after="serve/decode/put")
    assert skew is not None and skew["lo_ms"] <= skew["hi_ms"], skew
    mid = (skew["lo_ms"] + skew["hi_ms"]) / 2
    say("serve", f"traced pass: device clock + [{skew['lo_ms']:.3f}, "
                 f"{skew['hi_ms']:.3f}] ms = host clock over "
                 f"{skew['waits']} waits; corrected by the middle, launch "
                 f"gap p50 {skew['launch_ms_p50'] + mid:.3f} ms >= 0, wake "
                 f"gap p50 {skew['wake_ms_p50'] - mid:.3f} ms >= 0")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _close(name, got, want, tol):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite values"
    err = float(np.max(np.abs(got - want)))
    scale = max(1.0, float(np.max(np.abs(want))))
    assert err <= tol * scale, f"{name}: max |err| {err:.4g} > {tol} * {scale:.4g}"
    return err


def k_flash(g, interpret):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.monitor import stat_get
    from paddle_tpu.incubate.nn.attention_pallas import (_attn_ref,
                                                         flash_attention)

    b, h, s, d = g["attn_b"], g["heads"], g["seq"], g["head_dim"]
    rng = np.random.RandomState(SEED)
    q, k, v, w = (jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
                  for _ in range(4))
    scale = 1.0 / math.sqrt(d)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, True, scale, interpret=interpret)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

    def loss_ref(q, k, v):
        o = _attn_ref(q, k, v, True, scale)[1]
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

    paths = ("kernels/flash/resident", "kernels/flash/streamed")
    before = [stat_get(p) for p in paths]
    (_, o1), g1 = jax.jit(jax.value_and_grad(
        loss_flash, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, o2), g2 = jax.jit(jax.value_and_grad(
        loss_ref, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    errs = [_close("flash fwd", o1, o2, 2e-2)]
    errs += [_close(f"flash d{n}", a, b_, 3e-2)
             for n, a, b_ in zip("qkv", g1, g2)]
    resident, streamed = (stat_get(p) - n for p, n in zip(paths, before))
    return (f"fwd+bwd B{b} H{h} S{s} D{d} bf16, default blocks, max err "
            f"{max(errs):.3g}; calls resident {resident}, streamed "
            f"{streamed}")


def k_layernorm(g, interpret):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.nn.pallas import layernorm as ln

    n, h = g["rows"], g["hidden"]
    rng = np.random.RandomState(SEED)
    x = jnp.asarray(rng.randn(n, h), jnp.bfloat16)
    r = jnp.asarray(rng.randn(n, h), jnp.bfloat16)
    w = jnp.asarray(1.0 + 0.1 * rng.randn(h), jnp.float32)
    b = jnp.asarray(0.1 * rng.randn(h), jnp.float32)
    ct = jnp.asarray(rng.randn(n, h), jnp.float32)

    def ref(x, w, b, act):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * w + b
        if act == "gelu":
            y = jax.nn.gelu(y, approximate=True)
        return y.astype(x.dtype)

    errs = []
    for act in (None, "gelu"):
        def f_k(x, w, b):
            return jnp.sum(ln.fused_layer_norm(
                x, w, b, 1e-5, act, True, interpret).astype(jnp.float32) * ct)

        def f_r(x, w, b):
            return jnp.sum(ref(x, w, b, act).astype(jnp.float32) * ct)

        v1, g1 = jax.jit(jax.value_and_grad(f_k, (0, 1, 2)))(x, w, b)
        v2, g2 = jax.jit(jax.value_and_grad(f_r, (0, 1, 2)))(x, w, b)
        errs.append(_close(f"ln[{act}] fwd", v1 / n, v2 / n, 2e-2))
        errs.append(_close(f"ln[{act}] dx", g1[0], g2[0], 3e-2))
        # dw/db sum over all rows: compare relative to that scale
        errs.append(_close(f"ln[{act}] dw", g1[1] / math.sqrt(n),
                           g2[1] / math.sqrt(n), 3e-2))
        errs.append(_close(f"ln[{act}] db", g1[2] / math.sqrt(n),
                           g2[2] / math.sqrt(n), 3e-2))

    def f_k(x, r, w, b):
        y, s = ln.fused_residual_layer_norm(x, r, w, b, 1e-5, None, True,
                                            interpret)
        return jnp.sum(y.astype(jnp.float32) * ct) \
            + jnp.sum(s.astype(jnp.float32) * ct), (y, s)

    def f_r(x, r, w, b):
        s = x + r
        y = ref(s, w, b, None)
        return jnp.sum(y.astype(jnp.float32) * ct) \
            + jnp.sum(s.astype(jnp.float32) * ct), (y, s)

    (_, (y1, s1)), g1 = jax.jit(jax.value_and_grad(
        f_k, (0, 1, 2, 3), has_aux=True))(x, r, w, b)
    (_, (y2, s2)), g2 = jax.jit(jax.value_and_grad(
        f_r, (0, 1, 2, 3), has_aux=True))(x, r, w, b)
    errs.append(_close("ln+res y", y1, y2, 2e-2))
    errs.append(_close("ln+res sum", s1, s2, 1e-6))
    errs.append(_close("ln+res dx", g1[0], g2[0], 3e-2))
    errs.append(_close("ln+res dres", g1[1], g2[1], 3e-2))
    return f"fwd+bwd plain/gelu/residual rows {n} hidden {h} bf16, " \
           f"max err {max(errs):.3g}"


def k_optim(g, interpret):
    """Fused Adam/AdamW, SGD and Momentum through Optimizer.apply_gradients
    (the entry the train step calls) against the per-parameter loop."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu.optimizer as optim
    from paddle_tpu.incubate.nn.pallas import optim as fused

    h, f = g["hidden"], g["ffn"]
    rng = np.random.RandomState(SEED)
    shapes = {"fc1_w": (h, f), "fc1_b": (f,), "ln_w": (h,)}
    params = {n: jnp.asarray(0.02 * rng.randn(*s), jnp.float32)
              for n, s in shapes.items()}
    grads = {n: jnp.asarray(0.01 * rng.randn(*s), jnp.float32)
             for n, s in shapes.items()}
    lr = jnp.float32(1e-3)
    errs = []
    for name, make in (
            ("adamw", lambda: optim.AdamW(learning_rate=1e-3,
                                          weight_decay=0.01)),
            ("adam", lambda: optim.Adam(learning_rate=1e-3)),
            ("momentum", lambda: optim.Momentum(learning_rate=1e-3,
                                                momentum=0.9)),
            ("sgd", lambda: optim.SGD(learning_rate=1e-3))):
        opt = make()
        state = opt.init_state(params)

        def fused_step(p, gr, st):
            return fused.apply_fused(opt, p, gr, st, lr)

        os.environ["PADDLE_PALLAS_FUSION"] = "0"   # the per-parameter loop
        want_p, want_s = jax.jit(opt.apply_gradients)(params, grads, state,
                                                      lr)
        os.environ["PADDLE_PALLAS_FUSION"] = "1"
        got_p, got_s = jax.jit(fused_step)(params, grads, state)
        for n in shapes:
            errs.append(_close(f"{name} {n}", got_p[n], want_p[n], 1e-6))
            for slot in want_s[n]:
                errs.append(_close(f"{name} {n}.{slot}", got_s[n][slot],
                                   want_s[n][slot], 1e-6))
    chunks = sum(max(1, -(-int(np.prod(s)) // (fused.CHUNK_ROWS
                                               * fused.CHUNK_LANES)))
                 for s in shapes.values())
    return f"adamw/adam/momentum/sgd over {chunks} chunks, " \
           f"max err {max(errs):.3g}"


def _paged_inputs(g):
    import numpy as np
    import jax.numpy as jnp

    b, h, d, bs = g["serve_b"], g["heads"], g["head_dim"], g["block"]
    maxb = g["max_seq"] // bs
    n = b * maxb + 1
    rng = np.random.RandomState(SEED)
    k_pool = jnp.asarray(rng.randn(n, bs, h, d), jnp.bfloat16)
    v_pool = jnp.asarray(rng.randn(n, bs, h, d), jnp.bfloat16)
    # every sequence owns a disjoint run of blocks; slots past its context
    # hold the NULL block, as the engine's tables do
    lens = np.linspace(1, g["max_seq"] - 8, b).astype(np.int32)
    tables = np.zeros((b, maxb), np.int32)
    for i in range(b):
        used = -(-(int(lens[i]) + 8) // bs)
        tables[i, :used] = 1 + i * maxb + np.arange(used)
    return rng, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens)


def k_paged_decode(g, interpret):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.nn.pallas import paged_attention as pa

    rng, k_pool, v_pool, tables, lens = _paged_inputs(g)
    b, h, d = g["serve_b"], g["heads"], g["head_dim"]
    q = jnp.asarray(rng.randn(b, h, d), jnp.bfloat16)
    scale = 1.0 / math.sqrt(d)
    got = jax.jit(lambda *a: pa.paged_attention(
        *a, sm_scale=scale, interpret=interpret))(q, k_pool, v_pool, tables,
                                                  lens)
    want = pa.paged_attention_reference(q, k_pool, v_pool, tables, lens,
                                        sm_scale=scale)
    err = _close("paged decode", got, want, 2e-2)
    return f"B{b} H{h} D{d} block {g['block']} bf16, max err {err:.3g}"


def k_paged_verify(g, interpret):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.nn.pallas import paged_attention as pa

    rng, k_pool, v_pool, tables, lens = _paged_inputs(g)
    b, h, d, t = g["serve_b"], g["heads"], g["head_dim"], g["spec_t"]
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.bfloat16)
    scale = 1.0 / math.sqrt(d)
    got = jax.jit(lambda *a: pa.paged_attention_multi(
        *a, sm_scale=scale, interpret=interpret))(q, k_pool, v_pool, tables,
                                                  lens)
    want = pa.paged_attention_multi_reference(q, k_pool, v_pool, tables,
                                              lens, sm_scale=scale)
    err = _close("paged verify", got, want, 2e-2)
    return f"B{b} T{t} H{h} D{d} block {g['block']} bf16, max err {err:.3g}"


def k_paged_latent(g, interpret):
    """GLM-4.7-Flash's attention widths over one pool of 640-value
    rows: the absorbed form through the block tables against the
    same over a dense gather."""
    import types
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.text.models import mla

    cfg = types.SimpleNamespace(
        num_heads=20, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256)
    b, bs = g["serve_b"], g["block"]
    maxb = g["max_seq"] // bs
    rng = np.random.RandomState(SEED)

    def draw(*shape, scale):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.bfloat16)

    pool = draw(b * maxb + 1, bs, LATENT_ROW, scale=0.5)
    q_nope = draw(b, cfg.num_heads, cfg.qk_nope_head_dim, scale=0.3)
    q_rope = draw(b, cfg.num_heads, cfg.qk_rope_head_dim, scale=0.3)
    ap = {"wkv_b": draw(cfg.kv_lora_rank, cfg.num_heads * (
        cfg.qk_nope_head_dim + cfg.v_head_dim), scale=0.05)}
    lens = np.linspace(1, g["max_seq"], b).astype(np.int32)
    tables = np.zeros((b, maxb), np.int32)
    for i in range(b):
        used = -(-int(lens[i]) // bs)
        tables[i, :used] = 1 + i * maxb + np.arange(used)
    tables, lens = jnp.asarray(tables), jnp.asarray(lens)
    got = jax.jit(lambda *a: mla.mla_attend_paged(
        *a, ap, cfg, interpret=interpret))(q_nope, q_rope, pool, tables,
                                           lens)
    want = mla.mla_attend_absorbed(
        q_nope, q_rope, pool[tables].reshape(b, -1, LATENT_ROW), lens, ap,
        cfg)
    err = _close("paged latent", got, want, 2e-2)
    return f"B{b} H{cfg.num_heads} row {LATENT_ROW} block {bs} bf16, " \
           f"max err {err:.3g}"


def k_grouped_matmul(g, interpret):
    """LFM2-24B-A2B's decode rows and widths (1024 rows, hidden 2048
    -> 2 x 1536) over eight experts of a longer stack, against
    `jax.lax.ragged_dot`; two experts empty, one with a single row."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.nn.pallas import grouped_matmul as gm

    m, e, stack, first, k, n = g["grouped"]
    rng = np.random.RandomState(SEED)
    rows = jnp.asarray(rng.randn(m, k) * 0.5, jnp.bfloat16)
    w = jnp.asarray(rng.randn(stack, k, n) * 0.05, jnp.bfloat16)
    sizes = np.bincount(rng.randint(2, e, m - 1), minlength=e)
    sizes[1] = 1
    full = np.zeros(stack, np.int32)
    full[first:first + e] = sizes
    got = jax.jit(lambda *a: gm.grouped_matmul(*a, interpret=interpret))(
        rows, w, jnp.asarray(sizes, jnp.int32), jnp.int32(first))
    want = jax.lax.ragged_dot(rows, w, jnp.asarray(full))
    err = _close("grouped matmul", got, want, 5e-2)
    return f"rows {m} over {e} of {stack} groups, K {k} N {n} bf16, " \
           f"tiles {gm._tiles(m, e, k, n, 2)}, max err {err:.3g}"


def k_int8(g, interpret):
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.distributed.compress import DEFAULT_BLOCK, kernels

    rng = np.random.RandomState(SEED)
    n = g["hidden"] * g["ffn"] // 4
    flat = jnp.asarray(rng.randn(n), jnp.float32)
    flat = flat.at[:DEFAULT_BLOCK].set(0.0)          # an all-zero block
    q, s = kernels._quantize_pallas_i8(flat, DEFAULT_BLOCK, interpret)
    q_ref, s_ref = kernels.quantize_ref(flat, DEFAULT_BLOCK, "int8")
    assert q.dtype == jnp.int8 and bool(jnp.array_equal(q, q_ref)), \
        "int8 codes differ from quantize_ref"
    assert bool(jnp.array_equal(s, s_ref)), "scales differ from quantize_ref"
    x = kernels._dequantize_pallas_i8(q, s, DEFAULT_BLOCK, interpret)
    x_ref = kernels.dequantize_ref(q_ref, s_ref, DEFAULT_BLOCK, "int8")
    assert bool(jnp.array_equal(x, x_ref)), "dequantize differs from ref"
    return f"{n} elements in blocks of {DEFAULT_BLOCK}: codes, scales and " \
           f"dequantized values bit-identical to the jnp reference"


def _kernel_table():
    """(name, supported-on-this-backend predicate, check). The predicates
    are the ones the library's call sites select kernels by."""
    import paddle_tpu.incubate.nn.pallas as pallas
    from paddle_tpu.distributed.compress import DEFAULT_BLOCK
    from paddle_tpu.distributed.compress import kernels as qk
    from paddle_tpu.incubate.nn import attention

    def flash_ok(g):
        return attention._use_pallas(
            (g["attn_b"], g["heads"], g["seq"], g["head_dim"]), "bfloat16",
            False, 0.0)

    return [
        ("flash_attention", flash_ok, k_flash),
        ("fused_layer_norm", lambda g: pallas.ln_supported(g["hidden"]),
         k_layernorm),
        ("fused_optimizer", lambda g: pallas.optim_supported(), k_optim),
        ("paged_attention",
         lambda g: pallas.paged_attention.paged_decode_supported(
             g["heads"], g["head_dim"], g["block"]), k_paged_decode),
        ("paged_attention_multi",
         lambda g: pallas.paged_attention.paged_decode_supported(
             g["heads"], g["head_dim"], g["block"]), k_paged_verify),
        ("paged_latent_attention",
         lambda g: pallas.paged_attention.paged_decode_supported(
             1, LATENT_ROW, g["block"]), k_paged_latent),
        ("grouped_matmul",
         lambda g: pallas.grouped_matmul.grouped_matmul_supported(
             *g["grouped"][:2], *g["grouped"][4:], "bfloat16"),
         k_grouped_matmul),
        ("int8_block_quant", lambda g: qk._use_pallas("int8", DEFAULT_BLOCK), k_int8),
    ]


def phase_kernels(geom, interpret):
    os.environ["PADDLE_PALLAS_FUSION"] = "1"
    if interpret:
        os.environ["PADDLE_PALLAS_INTERPRET"] = "1"
    try:
        for name, supported, check in _kernel_table():
            if not interpret and not supported(geom):
                say("kernels", f"not supported on this chip: {name}")
                continue
            t0 = time.perf_counter()
            detail = check(geom, interpret)
            say("kernels", f"{name}: ok — {detail} "
                           f"({time.perf_counter() - t0:.1f} s with compile)")
    finally:
        os.environ.pop("PADDLE_PALLAS_FUSION", None)
        os.environ.pop("PADDLE_PALLAS_INTERPRET", None)


# ---------------------------------------------------------------------------
# multichip
# ---------------------------------------------------------------------------

def phase_multichip(width, per_chip_batch, seq):
    import jax
    from paddle_tpu.distributed import build_mesh, set_mesh
    from paddle_tpu.jit.distributed import DistributedTrainStepCompiler

    n = jax.device_count()
    if n < 4:
        say("multichip", f"skipped ({n} device)")
        return
    devices = jax.devices()[:4]
    batch = 4 * per_chip_batch

    # one-chip loss on the same global batch: forward only, a chip's worth
    # of rows at a time
    ref = _gpt(width, scan_unroll=1)
    loss_one = _eager_loss(ref, *_batch(width, batch, seq),
                           rows=per_chip_batch)
    del ref
    _free_device_memory()
    say("multichip", f"one-chip loss on the global batch of {batch}: "
                     f"{loss_one:.5f}")

    for axes in ({"dp": 4}, {"dp": 2, "mp": 2}):
        label = " x ".join(f"{k}={v}" for k, v in axes.items())
        model = _gpt(width, scan_unroll=1)
        ids, labels = _batch(width, batch, seq)
        mesh = build_mesh(axes, devices=devices)
        step = DistributedTrainStepCompiler(model, _adamw(model),
                                            loss_fn=None, mesh=mesh)
        t0 = time.perf_counter()
        loss0 = float(step(ids, labels).item())
        first_s = time.perf_counter() - t0
        losses = [loss0] + [float(step(ids, labels).item())
                            for _ in range(3)]
        t0 = time.perf_counter()
        losses.append(float(step(ids, labels).item()))
        step_s = time.perf_counter() - t0
        set_mesh(None)

        spread = set()
        for p in model.parameters():
            spread |= set(p._value.sharding.device_set)
        assert spread == set(devices), (label, spread)
        say("multichip", f"{label}: parameters on {len(spread)} devices; "
                         f"loss {losses[0]:.5f} -> {losses[-1]:.5f} "
                         f"(one chip {loss_one:.5f}); first call "
                         f"{first_s:.1f} s, last step {step_s * 1e3:.0f} ms")
        assert all(math.isfinite(v) for v in losses), losses
        assert losses[-1] < losses[0], losses
        assert abs(loss0 - loss_one) <= LOSS_TOL, (label, loss0, loss_one)
        del ids, labels              # eager inputs live on device 0
        if devices[0].platform != "cpu":     # the CPU client has no stats
            used = [d.memory_stats()["bytes_in_use"] for d in devices]
            mean = sum(used) / len(used)
            say("multichip", f"{label}: bytes_in_use per device "
                             f"{[round(u / 2**30, 2) for u in used]} GiB")
            assert max(used) <= 1.5 * mean, (label, used)
        del step, model
        _free_device_memory()


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def main(argv=None):
    global _PREFLIGHT
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--preflight", action="store_true",
                    help="CPU rehearsal at toy width; not a chip run")
    args = ap.parse_args(argv)
    _PREFLIGHT = args.preflight
    if _PREFLIGHT:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()

    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not _PREFLIGHT and dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}; "
              "--preflight rehearses on the CPU", file=sys.stderr)
        return 2

    import paddle_tpu
    from paddle_tpu.jit import persistent_cache

    here = os.path.dirname(os.path.abspath(__file__))
    if os.path.dirname(os.path.abspath(paddle_tpu.__file__)) != \
            os.path.join(here, "paddle_tpu"):
        print(f"chip_smoke: paddle_tpu was imported from "
              f"{paddle_tpu.__file__}, not from the checkout that holds "
              "this script", file=sys.stderr)
        return 2

    t_start = time.perf_counter()
    cache_dir = persistent_cache.arm_native()
    say("env", f"device {device}; jax {jax.__version__} jaxlib "
               f"{jaxlib.__version__} libtpu {_version('libtpu')}")
    say("env", f"compile cache {cache_dir} "
               f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
               f" entries at start)")

    width = TOY_WIDTH if _PREFLIGHT else WIDTH
    train = TOY_TRAIN if _PREFLIGHT else TRAIN
    serve = TOY_SERVE if _PREFLIGHT else SERVE
    head_dim = width["hidden_size"] // width["num_heads"]
    geom = dict(attn_b=2, heads=width["num_heads"], seq=train["seq"],
                head_dim=head_dim, rows=train["batch"] * train["seq"],
                hidden=width["hidden_size"], ffn=width["ffn_hidden"],
                serve_b=serve["max_batch"], block=16,
                max_seq=width["max_seq_len"], spec_t=4,
                grouped=TOY_GROUPED_SHAPE if _PREFLIGHT else GROUPED_SHAPE)

    t = phase_train(width, **train)
    _free_device_memory()
    s = phase_serve(width, **serve)
    _free_device_memory()
    phase_kernels(geom, interpret=_PREFLIGHT)
    _free_device_memory()
    phase_multichip(width, train["batch"], train["seq"])

    native = persistent_cache.native_cache_stats()
    say("env", f"compile cache {native['dir']}: {native['requests']} "
               f"requests, {native['hits']} hits, {native['misses']} misses")
    say("env", f"compile seconds: train {t['compile_s']:.1f}, serve "
               f"{s['compile_s']:.1f}; whole run "
               f"{time.perf_counter() - t_start:.0f} s")
    if _PREFLIGHT:
        print("preflight ok — not a chip run; no result line", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
