"""Benchmark entry (driver contract): prints ONE JSON line
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}.

Covers all five BASELINE.md configs:
  1. MNIST LeNet        — imgs/s, compiled train step (f32)
  2. ResNet-50          — imgs/s, SGD+momentum, O2 bf16 (BN stays f32)
  3. BERT-base pretrain — tokens/s, Pallas flash-attention path
  4. GPT-2 345M         — tokens/s (flagship; the headline metric)
  5. ERNIE hybrid       — tokens/s through DistributedTrainStepCompiler
                          (mp+pp machinery; single-chip mesh here)

All half-precision configs use the reference's O2 numerics: bf16
weights with fp32 master weights in the optimizer
(multi_precision=True), norm layers kept f32 via amp.decorate. Every
config asserts its loss decreased over the measured window.

vs_baseline ratios use documented V100 stand-ins (BASELINE.md: the
reference repo publishes no numbers, so these constants are the
recorded "CUDAPlace/V100" proxies; north star >= 1/1.2 of them):
  GPT-2 345M fp16   ~12,000 tokens/s/GPU (Megatron-LM V100 measurements)
  ResNet-50 AMP     ~780 imgs/s/GPU (MLPerf-era V100 fp16)
  BERT-base fp16    ~25,000 tokens/s/GPU (NVIDIA BERT repo, seq 512)
  ERNIE-base fp16   ~25,000 tokens/s/GPU (BERT-base-shaped proxy)
  LeNet MNIST       ~10,000 imgs/s (dygraph dispatch-bound V100 proxy)
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINES = {
    "gpt2_345m": 12000.0,
    "resnet50": 780.0,
    "resnet50_pipeline": 780.0,
    "bert_base": 25000.0,
    "ernie": 25000.0,
    "mnist_lenet": 10000.0,
}


WINDOWS = 5  # median-of-k windows: the median of five independent
# windows is the recorded number and the spread is reported


def _measure(step, args, steps, warmup):
    """Median of WINDOWS timing windows, `steps` timed steps each.
    Returns (dt_per_step, first_loss, last_loss, window_dts)."""
    for _ in range(warmup):
        loss = step(*args)
    first = float(loss.item())
    dts = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(*args)
        last = float(loss.item())  # .item() syncs
        dts.append((time.perf_counter() - t0) / steps)
    return float(np.median(dts)), first, last, dts


def peak_tflops():
    """Peak bf16 chip TF/s for the MFU column. BENCH_PEAK_TFLOPS
    still wins (back-compat with older trail records), otherwise the
    monitor/perf device-kind table supplies it — the SAME source the
    per-program MFU in extra.perf uses, so the two columns can never
    disagree on the peak (ISSUE 16)."""
    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env)
    from paddle_tpu.monitor import perf as _perf

    return float(_perf.device_peaks()["peak_tflops"])


def _param_count(model):
    return sum(int(np.prod(p.shape)) for p in model.parameters())


def _mfu(flops_per_step, dt):
    """Model FLOPs utilization against peak_tflops(). For transformers
    flops = 6*N*tokens (param FLOPs, fwd+bwd); convnets use published
    per-image forward GFLOPs x3."""
    return round(flops_per_step / dt / (peak_tflops() * 1e12), 4)


def _pack(value, unit, dts, mfu=None, program=None, flops=None):
    r = {"value": value, "unit": unit,
         "window_spread": [round(d, 6) for d in dts]}
    if mfu is not None:
        r["mfu"] = mfu
    if program is not None:
        # ties the config row to its perf/program/* ledger entry so
        # extra.perf can price analytic-vs-compiler FLOPs drift
        r["program"] = program
        r["analytic_flops_per_step"] = flops
    return r


def _check_decreasing(name, first, last):
    assert np.isfinite(last), f"{name}: non-finite loss {last}"
    assert last < first, (
        f"{name}: loss did not decrease over the bench window "
        f"({first:.4f} -> {last:.4f})")


def bench_mnist(on_tpu):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStepCompiler
    from paddle_tpu.vision.models import LeNet

    # the step is host-latency-bound at this model size; B=1024 and
    # >=60 timed steps x 5 windows amortize the dispatch jitter
    paddle.seed(0)
    batch = 1024 if on_tpu else 32
    steps, warmup = (100, 5) if on_tpu else (3, 1)
    net = LeNet()
    ce = nn.CrossEntropyLoss()
    opt = optim.Adam(learning_rate=1e-3, parameters=net.parameters())
    step = TrainStepCompiler(net, opt,
                             lambda o, y: ce(o, y))
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (batch,)).astype(np.int64))
    dt, first, last, dts = _measure(step, (x, y), steps, warmup)
    _check_decreasing("mnist", first, last)
    # LeNet fwd ~= 0.00042 GF/img (published MACs x2), fwd+bwd ~3x
    fl = 3 * 0.00042e9 * batch
    r = _pack(round(batch / dt, 1), "imgs/s", dts, _mfu(fl, dt),
              program=step._perf_name, flops=fl)
    r["note"] = ("dispatch latency probe: at this model size the "
                 "number measures the host round-trip, not the "
                 "framework — do not read vs_baseline as a win")

    # fused multi-step dispatch: K train steps scanned through ONE XLA
    # program (steps_per_dispatch) — the per-step host round-trip this
    # probe is bound by amortizes over K, so the ratio
    # fused/vs-unfused IS the dispatch overhead the r5 verdict flagged
    K = 8
    paddle.seed(0)
    net_f = LeNet()
    opt_f = optim.Adam(learning_rate=1e-3,
                       parameters=net_f.parameters())
    step_f = TrainStepCompiler(net_f, opt_f, lambda o, y: ce(o, y),
                               steps_per_dispatch=K)
    xs = paddle.to_tensor(
        rng.randn(K, batch, 1, 28, 28).astype(np.float32))
    ys = paddle.to_tensor(
        rng.randint(0, 10, (K, batch)).astype(np.int64))
    n_disp = max(1, steps // K)
    for _ in range(max(1, warmup // 2)):
        lv = step_f(xs, ys)
    first_f = float(np.asarray(lv._value)[0])
    dts_f = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(n_disp):
            lv = step_f(xs, ys)
        last_f = float(np.asarray(lv._value)[-1])  # sync
        dts_f.append((time.perf_counter() - t0) / n_disp)
    _check_decreasing("mnist_fused", first_f, last_f)
    dt_f = float(np.median(dts_f))
    r["steps_per_dispatch"] = K
    r["fused_imgs_s"] = round(batch * K / dt_f, 1)
    r["fused_speedup"] = round((batch * K / dt_f) / (batch / dt), 3)

    # async-checkpoint robustness tax (ISSUE 6): the SAME plain step
    # loop, now snapshotting full training state (params + live opt
    # slots) through the background writer every CKPT_EVERY steps —
    # still far more aggressive than any production cadence (the EDL
    # default is time-based, 900 s). The delta vs the plain loop
    # above is the elastic-checkpointing overhead the trajectory
    # tracks (<2% target; the step-boundary device->host copy is the
    # only on-thread cost, serialization + disk ride the writer
    # thread).
    import shutil
    import tempfile

    from paddle_tpu.incubate.checkpoint import CheckpointManager

    CKPT_EVERY = 10
    ck_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    mgr = CheckpointManager(dir=ck_dir, save_steps=CKPT_EVERY,
                            max_num=2, async_write=True)
    try:
        g = 0
        for _ in range(warmup):
            loss = step(x, y)
        dts_c = []
        for _ in range(WINDOWS):
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = step(x, y)
                g += 1
                mgr.maybe_save(
                    lambda: {"model": dict(net.state_dict()),
                             "slots": step._opt_state},
                    global_step=g)
            float(loss.item())  # sync
            dts_c.append((time.perf_counter() - t0) / steps)
        dt_c = float(np.median(dts_c))
        r["ckpt_save_steps"] = CKPT_EVERY
        r["ckpt_async_imgs_s"] = round(batch / dt_c, 1)
        r["ckpt_overhead_pct"] = round((dt_c / dt - 1) * 100, 2)
    finally:
        mgr.close()
        shutil.rmtree(ck_dir, ignore_errors=True)

    # live introspection tax (ISSUE 18): the SAME plain step loop
    # with the debug server armed on an ephemeral port — the delta
    # vs the plain loop proves the serve thread is off the hot path
    # (an idle accept() should be unmeasurable; measured, not
    # assumed)
    from paddle_tpu.monitor import server as _mserver

    srv = None
    try:
        srv = _mserver.serve(port=0, host="127.0.0.1")
    except OSError:
        pass
    if srv is not None:
        try:
            for _ in range(warmup):
                loss = step(x, y)
            dts_s = []
            for _ in range(WINDOWS):
                t0 = time.perf_counter()
                for _ in range(steps):
                    loss = step(x, y)
                float(loss.item())  # sync
                dts_s.append((time.perf_counter() - t0) / steps)
            dt_s = float(np.median(dts_s))
            r["serve_port"] = srv.port
            r["serve_imgs_s"] = round(batch / dt_s, 1)
            r["serve_overhead_pct"] = round((dt_s / dt - 1) * 100, 2)
        finally:
            _mserver.stop_server()
    return r


def bench_resnet50(on_tpu):
    # r3 probe notes (v5e single chip): NHWC == NCHW e2e (XLA:TPU
    # canonicalizes conv layouts; measured 2294 vs 2291 imgs/s), so the
    # gains came from (a) one-pass BN statistics (E[x],E[x^2] fused into
    # one activation read, ops/norm_ops.py) ~+9%, (b) batch 64->128
    # ~+17%. r5: framework measures AT raw-XLA parity — pure-jax NHWC
    # resnet50 (benchmarks/parity_resnet_jax.py) records 2,682 imgs/s
    # on the same chip vs 2,621 through the full framework (−2.3%);
    # B=256 (2,572) and B=192 (2,431) are no faster, and the step
    # profile (benchmarks/profile_resnet50.py) showed
    # the time in BN-stat reductions + conv fusions — the remaining
    # MFU gap is XLA:TPU's conv pipeline, not framework overhead.
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStepCompiler
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    batch = 128 if on_tpu else 2
    size = 224 if on_tpu else 32
    steps, warmup = (60, 5) if on_tpu else (2, 1)  # r3 weak #1: 20
    # timed steps was inside the jitter envelope; 60 x 5 windows
    net = resnet50()
    if on_tpu:
        net = amp.decorate(net, level="O2", dtype="bfloat16")
    ce = nn.CrossEntropyLoss()
    opt = optim.Momentum(learning_rate=0.01, momentum=0.9,
                         parameters=net.parameters(),
                         multi_precision=on_tpu)
    step = TrainStepCompiler(net, opt, lambda o, y: ce(o, y))
    rng = np.random.RandomState(0)
    import jax.numpy as jnp

    dt_in = jnp.bfloat16 if on_tpu else jnp.float32
    x = paddle.to_tensor(
        rng.randn(batch, 3, size, size).astype(np.float32))
    x._value = x._value.astype(dt_in)
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype(np.int64))
    dt, first, last, dts = _measure(step, (x, y), steps, warmup)
    _check_decreasing("resnet50", first, last)
    # ResNet-50 fwd 4.09 GF/img at 224x224 (published), fwd+bwd ~3x
    fl = 3 * 4.09e9 * batch
    return _pack(round(batch / dt, 1), "imgs/s", dts, _mfu(fl, dt),
                 program=step._perf_name, flops=fl)


class _SynthImageNet:
    """ImageNet-shaped synthetic dataset for the pipeline-fed bench:
    one preallocated image per worker (index-cheap __getitem__), so
    the measured cost is collation + shm-ring transport + H2D — the
    DataLoader machinery itself — not numpy RNG throughput."""

    def __init__(self, n, size):
        rng = np.random.RandomState(0)
        self.n = n
        self.base = rng.randn(3, size, size).astype(np.float32)
        self.labels = rng.randint(0, 1000, (n,)).astype(np.int64)

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return self.base, self.labels[i]


def bench_resnet50_pipeline(on_tpu):
    """Pipeline-fed config (r4 weak #2 made this honest).

    Three measurements:
      * loader_view_imgs_s — zero-copy delivery rate of the
        multiprocess shm-ring machinery (4 workers): batches stack
        directly into ring slots and deserialize as slot views
        (protocol-5 out-of-band), trainer touches each batch. This is
        the DataLoader-machinery rate.
      * loader_imgs_s — same loader with user-OWNED batches (one
        detach memcpy per batch). The claim "the input pipeline
        sustains the synthetic device rate" is tested against THIS
        number; when the host can't reach it the note records the
        measured shortfall and the host core count (a 77 MB/batch
        pipeline needs at least one host copy; on a single-core bench
        host that copy bounds the rate regardless of worker count).
      * value (e2e imgs/s) — the same loader FEEDING the compiled
        step, per-step H2D of the 77 MB batch included.
    """
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.io import DataLoader
    from paddle_tpu.jit import TrainStepCompiler
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    batch = 128 if on_tpu else 2
    size = 224 if on_tpu else 32
    net = resnet50()
    if on_tpu:
        net = amp.decorate(net, level="O2", dtype="bfloat16")
    ce = nn.CrossEntropyLoss()
    opt = optim.Momentum(learning_rate=0.01, momentum=0.9,
                         parameters=net.parameters(),
                         multi_precision=on_tpu)
    step = TrainStepCompiler(net, opt, lambda o, y: ce(o, y))
    import os

    import jax.numpy as jnp

    dt_in = jnp.bfloat16 if on_tpu else jnp.float32
    # 128x3x224x224 f32 = 77 MB/batch: needs a bigger shm-ring slot
    # than the 64 MB default
    os.environ.setdefault("FLAGS_dataloader_shm_slot_mb", "128")
    n_loader = 40 if on_tpu else 4
    warm_l = 5 if on_tpu else 1
    ds = _SynthImageNet((n_loader + warm_l) * batch, size)

    def _np_collate_pair(b):
        xs, ys = zip(*b)
        return np.stack(xs), np.stack(ys)

    # worker pool auto-sized from the host (ISSUE 8: saturate a
    # multi-core host without per-machine tuning)
    from paddle_tpu.io import _auto_num_workers

    n_workers = _auto_num_workers()

    # (1a) machinery rate: zero-copy slot views straight off the rings
    from paddle_tpu.io.worker import MultiprocessLoader

    mpl = MultiprocessLoader(ds, _np_collate_pair, n_workers, 2, 128,
                             None, 0, False, batch_size=batch,
                             default_collate=True)
    idx = [list(range(i * batch, (i + 1) * batch))
           for i in range(n_loader + warm_l)]
    gen = mpl.run_epoch(idx)
    for _ in range(warm_l):
        next(gen)
    t0 = time.perf_counter()
    got = 0
    for xb, yb in gen:
        got += 1
        _ = xb[0, 0, 0, 0]  # touch: the view is real delivered data
    view_dt = (time.perf_counter() - t0) / max(got, 1)
    mpl.shutdown()
    view_rate = round(batch / view_dt, 1)

    # (1b) user-owned host delivery rate (one detach memcpy per batch)
    loader_host = DataLoader(ds, batch_size=batch, num_workers=-1,
                             use_shared_memory=True, drop_last=True,
                             collate_fn=_np_collate_pair)
    it = iter(loader_host)
    for _ in range(warm_l):
        next(it)
    t0 = time.perf_counter()
    got = 0
    for x, y in it:
        got += 1
    loader_dt = (time.perf_counter() - t0) / max(got, 1)
    loader_rate = round(batch / loader_dt, 1)

    loader = DataLoader(ds, batch_size=batch, num_workers=-1,
                        use_shared_memory=True, drop_last=True,
                        persistent_workers=True,
                        prefetch_to_device=2)
    # (2) e2e: loader feeding the compiled step through the async
    # device-feed stage (prefetch_to_device=2): H2D for batch i+1
    # issues from a background thread while the chip runs batch i
    # (few steps — each carries a 77 MB H2D)
    steps, warmup, windows = (4, 1, 2) if on_tpu else (2, 1, 1)
    it = iter(loader)
    dts = []

    def _next_step():
        nonlocal it
        try:
            x, y = next(it)
        except StopIteration:
            it = iter(loader)
            x, y = next(it)
        x._value = x._value.astype(dt_in)
        return step(x, y)

    for _ in range(warmup):
        loss = _next_step()
    first = float(loss.item())
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = _next_step()
        last = float(loss.item())
        dts.append((time.perf_counter() - t0) / steps)
    _check_decreasing("resnet50_pipeline", first, last)
    dt = float(np.median(dts))
    # MFU for the pipeline-fed config too (ISSUE 8: MFU per config) —
    # same per-image FLOPs as the synthetic resnet50 config
    fl = 3 * 4.09e9 * batch
    r = _pack(round(batch / dt, 1), "imgs/s", dts, _mfu(fl, dt),
              program=step._perf_name, flops=fl)
    r["loader_view_imgs_s"] = view_rate
    r["loader_imgs_s"] = loader_rate
    r["host_cpus"] = os.cpu_count()
    r["loader_workers"] = n_workers
    r["prefetch_to_device"] = 2
    # the sustains-the-device-rate claim is checked, not asserted:
    # record truthfully whether the owned-batch rate meets the
    # synthetic device rate measured by the resnet50 config (r4 weak
    # #2: the note previously CLAIMED it while the number refuted it)
    r["note"] = (
        "loader_view_imgs_s = shm-ring machinery (zero-copy views); "
        "loader_imgs_s = user-owned batches (one detach copy) — "
        "compare THIS to the resnet50 config's imgs/s for the "
        "sustains-the-device-rate claim; on a single-core bench host "
        "the mandatory per-batch copies bound it regardless of worker "
        "count.")
    return r


def bench_bert(on_tpu):
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStepCompiler
    from paddle_tpu.text.models.bert import BertConfig, BertForPretraining

    # r3 probe: batch 8->32 amortizes the fixed per-step cost
    # (68.7k -> 71.5k tok/s); hidden-768 matmuls are the ceiling
    # (K~=hidden GEMMs measure ~45-60 TF/s on this chip vs 147+ at
    # K=4096).
    paddle.seed(0)
    if on_tpu:
        cfg = BertConfig(dropout=0.0)  # bert-base
        batch, seq, steps, warmup = 32, 512, 12, 3
    else:
        cfg = BertConfig(vocab_size=512, hidden_size=128, num_layers=2,
                         num_heads=2, ffn_hidden=256, max_seq_len=128,
                         dropout=0.0)
        batch, seq, steps, warmup = 2, 128, 2, 1
    import paddle_tpu.nn as nn

    class BertPretrainStep(nn.Layer):
        """Fixed-signature wrapper so the whole batch is jit-traceable."""

        def __init__(self, cfg):
            super().__init__()
            self.m = BertForPretraining(cfg)

        def forward(self, ids, tt, labels):
            return self.m(ids, token_type_ids=tt, masked_lm_labels=labels)

    model = BertPretrainStep(cfg)
    if on_tpu:
        model = amp.decorate(model, level="O2", dtype="bfloat16")
    opt = optim.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                      weight_decay=0.01, multi_precision=on_tpu)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                       (batch, seq)).astype(np.int64))
    step = TrainStepCompiler(model, opt, loss_fn=None)
    tt = paddle.to_tensor(np.zeros((batch, seq), np.int64))
    dt, first, last, dts = _measure(step, (ids, tt, ids), steps, warmup)
    _check_decreasing("bert", first, last)
    fl = 6 * _param_count(model) * batch * seq
    return _pack(round(batch * seq / dt, 1), "tokens/s", dts,
                 _mfu(fl, dt), program=step._perf_name, flops=fl)


def bench_gpt2(on_tpu):
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStepCompiler
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    # r5 sweep (benchmarks/exp_gpt2.py): scan_unroll=24 (full unroll of
    # the layer stack) is worth +18% over the scan — the r4 profile's
    # 45% "scan body" share carried ~1.4 ms/iteration of loop overhead
    # plus dynamic-update-slice traffic saving residuals; unrolled, XLA
    # schedules across layer boundaries. Partial unroll is WORSE (u4:
    # 18.5k) and u8 OOMs. remat=False at B=4 still beats remat at
    # larger B (r3); B=6 is step-linear (no gain). CE is
    # logsumexp-gather (no [B,S,V] f32 materialization).
    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                        num_heads=16, ffn_hidden=4096, max_seq_len=1024,
                        dropout=0.0, remat=False, use_flash_attention=True,
                        scan_unroll=24)
        batch, seq, steps, warmup = 4, 1024, 20, 3  # x5 windows
    else:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, ffn_hidden=256, max_seq_len=128,
                        dropout=0.0, remat=False, use_flash_attention=False)
        batch, seq, steps, warmup = 4, 128, 5, 1

    model = GPTForCausalLM(cfg)
    if on_tpu:
        model = amp.decorate(model, level="O2", dtype="bfloat16")
    opt = optim.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                      weight_decay=0.01, multi_precision=on_tpu)
    step = TrainStepCompiler(model, opt, loss_fn=None)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                       (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                          (batch, seq)).astype(np.int32))
    dt, first, last, dts = _measure(step, (ids, labels), steps, warmup)
    _check_decreasing("gpt2", first, last)
    fl = 6 * _param_count(model) * batch * seq
    return _pack(round(batch * seq / dt, 1), "tokens/s", dts,
                 _mfu(fl, dt), program=step._perf_name, flops=fl)


def bench_ernie(on_tpu):
    """ERNIE through the hybrid-parallel compiler (BASELINE config 5:
    Fleet mp+pp). On a single chip the mesh is 1-device (mp=pp=1) —
    the same code path the multichip dryrun runs with real axes."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.optimizer as optim
    from paddle_tpu.distributed import build_mesh, set_mesh
    from paddle_tpu.jit.distributed import DistributedTrainStepCompiler
    from paddle_tpu.text.models.ernie import (ErnieConfig,
                                              ErnieForPretraining)

    # r3 probe: batch sweep peaked at B=8 (77.1k); r5 re-sweep with the
    # full-sequence flash blocks moved the optimum: A/B/A/B measured
    # B=12 at 87.2k twice vs B=8 at 83-85k (+~3.5%) — the faster
    # attention shifted the per-step fixed-cost balance.
    paddle.seed(0)
    if on_tpu:
        cfg = ErnieConfig(vocab_size=18000, hidden_size=768,
                          num_layers=12, num_heads=12, ffn_hidden=3072,
                          max_seq_len=512, dropout=0.0)
        batch, seq, steps, warmup = 12, 512, 15, 3
    else:
        cfg = ErnieConfig(vocab_size=512, hidden_size=128, num_layers=2,
                          num_heads=2, ffn_hidden=256, max_seq_len=128,
                          dropout=0.0)
        batch, seq, steps, warmup = 2, 128, 2, 1
    model = ErnieForPretraining(cfg)
    if on_tpu:
        model = amp.decorate(model, level="O2", dtype="bfloat16")
    opt = optim.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                      weight_decay=0.01, multi_precision=on_tpu)
    mesh = build_mesh({"dp": 1, "pp": 1, "mp": -1})
    set_mesh(mesh)
    step = DistributedTrainStepCompiler(model, opt, loss_fn=None,
                                        mesh=mesh)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                       (batch, seq)).astype(np.int64))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                          (batch, seq)).astype(np.int64))
    dt, first, last, dts = _measure(step, (ids, labels), steps, warmup)
    _check_decreasing("ernie", first, last)
    set_mesh(None)
    fl = 6 * _param_count(model) * batch * seq
    return _pack(round(batch * seq / dt, 1), "tokens/s", dts,
                 _mfu(fl, dt), program=step._perf_name, flops=fl)


def _itl_ms(gaps):
    """p50/p99 inter-token latency (ms) off raw second-gaps — ONE
    implementation for the serving config and its resilience twin
    (ISSUE 15 satellite; previously two ad-hoc sorted-list copies).
    Routes through monitor.Histogram and ASSERTS the histogram
    quantiles agree with the sorted-list convention they replaced on
    the same data, within one log-bucket of resolution — so the
    Histogram the runtime exports is provably the number the bench
    used to report."""
    from paddle_tpu.core.monitor import Histogram

    h = Histogram("bench/itl_us")
    for g in gaps:
        h.observe(g * 1e6)
    sg = sorted(gaps) or [0.0]
    out = {}
    for key, q in (("itl_p50_ms", 0.5), ("itl_p99_ms", 0.99)):
        exact_ms = 1e3 * sg[min(len(sg) - 1, int(len(sg) * q))]
        hist_ms = h.quantile(q) / 1e3 if gaps else 0.0
        # one bucket's width of tolerance (plus 10us of float slack
        # for near-zero CPU-smoke gaps)
        ratio = 10.0 ** (1.0 / h.per_decade)
        assert (hist_ms <= exact_ms * ratio + 0.01
                and hist_ms >= exact_ms / ratio - 0.01), (
            f"histogram {key} {hist_ms}ms disagrees with sorted-list "
            f"{exact_ms}ms beyond one bucket ({ratio:.3f}x)")
        out[key] = round(hist_ms, 3)
    return out


def _bench_serve_spec(model, prompts, sampling, max_batch, spec_k=4):
    """ISSUE 19 twin: the SAME mixed-length request set (plus a
    shared 48-token system prefix, so the prefix cache has full
    blocks to share) through (a) a plain k=1/no-cache engine and
    (b) a speculative-decoding + prefix-caching engine. Both are
    measured at steady state — wave 2 of the same engine, after
    wave 1 paid the XLA compiles and published the shareable prefix
    blocks — the regime a long-lived serving replica actually runs
    in. Reports tokens/s + p50/p99 ITL for both, acceptance rate and
    prefill-tokens-saved; the emitted tokens are asserted identical
    to the k=1 baseline, the house discipline."""
    from paddle_tpu.core import monitor as _cmon
    from paddle_tpu.inference.serving import LLMEngine

    rng = np.random.RandomState(19)
    vocab = model.config.vocab_size
    prefix = list(rng.randint(1, vocab, 48))
    twin_prompts = [prefix + list(p) for p in prompts]

    def run(**kw):
        eng = LLMEngine(model, max_batch=max_batch, **kw)

        def wave():
            ids = [eng.add_request(p, sampling=sampling)
                   for p in twin_prompts]
            t0 = time.perf_counter()
            while eng.has_unfinished():
                eng.step()
            dt = time.perf_counter() - t0
            gaps, outs = [], []
            for i in ids:
                req = eng.get_request(i)
                ts = req.token_times
                gaps.extend(b - a for a, b in zip(ts, ts[1:]))
                outs.append(req.output_ids)
            return outs, gaps, dt

        wave()               # compiles + prefix-block registration
        outs, gaps, dt = wave()
        assert not eng.check_drained(), "spec twin leaked KV blocks"
        return outs, gaps, dt, sum(len(o) for o in outs) / dt

    base_outs, base_gaps, _, base_tps = run()
    keys = ("serve/spec/proposed", "serve/spec/accepted",
            "serve/prefix/hits", "serve/prefix/blocks_shared",
            "serve/prefix/prefill_tokens_saved")
    before = {k: _cmon.stat_get(k) for k in keys}
    spec_outs, spec_gaps, spec_dt, spec_tps = run(
        spec_k=spec_k, prefix_cache=True)
    assert spec_outs == base_outs, \
        "speculative/prefix twin diverged from the greedy baseline"
    d = {k: _cmon.stat_get(k) - before[k] for k in keys}
    assert spec_tps > base_tps, (
        f"speculative decoding did not improve steady-state "
        f"throughput: {spec_tps:.1f} vs {base_tps:.1f} tokens/s")
    out = {"value": round(spec_tps, 1), "unit": "tokens/s",
           "window_spread": [round(spec_dt, 6)],
           "spec_k": spec_k,
           "baseline_tokens_s": round(base_tps, 1),
           "speedup_vs_k1": round(spec_tps / base_tps, 3),
           "accept_rate": round(
               d["serve/spec/accepted"]
               / max(1, d["serve/spec/proposed"]), 4),
           "proposed": d["serve/spec/proposed"],
           "accepted": d["serve/spec/accepted"],
           "prefix_hits": d["serve/prefix/hits"],
           "blocks_shared": d["serve/prefix/blocks_shared"],
           "prefill_tokens_saved":
               d["serve/prefix/prefill_tokens_saved"]}
    out.update(_itl_ms(spec_gaps))
    base_itl = _itl_ms(base_gaps)
    out["baseline_itl_p50_ms"] = base_itl["itl_p50_ms"]
    out["baseline_itl_p99_ms"] = base_itl["itl_p99_ms"]
    return out


def bench_serving(on_tpu):
    """ISSUE 11: the serving engine under mixed-length generation
    traffic — continuous batching (the LLMEngine default) against a
    static-batching twin (admit a batch, drain it, admit the next),
    same requests, same pools. Reports generated tokens/s plus the
    p50/p99 INTER-TOKEN latency the scheduler's interleaving policy
    actually delivers to a streaming client. Grows two riders: the
    ISSUE-13 goodput-under-chaos twin and the ISSUE-19 speculative-
    decoding + prefix-caching twin (`_bench_serve_spec`, embedded as
    extra.serve_spec by main())."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import LLMEngine, SamplingParams
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024,
                        num_layers=24, num_heads=16, ffn_hidden=4096,
                        max_seq_len=1024, dropout=0.0,
                        use_flash_attention=True)
        lens, new_tokens, max_batch = (16, 64, 192, 384, 17, 96,
                                       256, 33), 64, 8
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                        num_heads=4, ffn_hidden=128, max_seq_len=128,
                        dropout=0.0, use_flash_attention=False)
        lens, new_tokens, max_batch = (3, 17, 9, 33, 5, 24, 12,
                                       7), 12, 4
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, cfg.vocab_size, n)) for n in lens]
    sampling = SamplingParams(max_new_tokens=new_tokens)

    def run(static):
        eng = LLMEngine(model, max_batch=max_batch,
                        static_batching=static)
        ids = [eng.add_request(p, sampling=sampling) for p in prompts]
        t0 = time.perf_counter()
        while eng.has_unfinished():
            eng.step()
        dt = time.perf_counter() - t0
        gaps = []
        for i in ids:
            ts = eng.get_request(i).token_times
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        total = sum(len(eng.get_request(i).output_ids) for i in ids)
        assert not eng.check_drained(), "bench leaked KV blocks"
        return total / dt, gaps, dt

    cb_tps, gaps, cb_dt = run(static=False)
    sb_tps, _, _ = run(static=True)
    r = _pack(round(cb_tps, 1), "tokens/s", [cb_dt])
    r.update(_itl_ms(gaps))
    r["static_batching_tokens_s"] = round(sb_tps, 1)
    r["cb_vs_static"] = round(cb_tps / sb_tps, 3) if sb_tps else 0.0

    # disarmed-path provenance (ISSUE 19): the baseline runs above
    # never armed speculation or prefix caching, so they must leave
    # ZERO serve/spec/* + serve/prefix/* counters behind — the same
    # zero-overhead contract the sanitize/chaos gates enforce
    from paddle_tpu.core import monitor as _cmon
    leaked = {k: v for k, v in _cmon.registry.snapshot().items()
              if k.startswith(("serve/spec/", "serve/prefix/"))}
    assert not leaked, (
        "k=1/no-cache serving runs left spec/prefix counters behind "
        f"(disarmed paths must be free): {leaked}")
    r["spec"] = _bench_serve_spec(model, prompts, sampling, max_batch)

    # ISSUE-13 goodput-under-chaos twin: the SAME traffic through a
    # 2-replica Router with a serve_decode fault storm armed (OOM
    # churn + one replica kill) and tight queues — tokens/s, p50/p99
    # inter-token latency, shed rate and failover count, against the
    # clean continuous-batching number above. Embedded as
    # extra.serve_resilience by main(), so every perf record is
    # provably chaos-annotated (which faults, how many triggers, and
    # what they cost).
    from paddle_tpu.inference.serving import (EngineOverloaded,
                                              Router)
    from paddle_tpu.monitor import chaos as _chaos

    keys = ("serve/shed", "serve/failovers", "serve/drains",
            "serve/deadline_aborts", "serve/oom_evictions")
    base = {k: _cmon.stat_get(k) for k in keys}
    router = Router(model, replicas=2, max_batch=max(2, max_batch // 2),
                    max_queue=1)
    sheds = 0
    try:
        t0 = time.perf_counter()
        with _chaos.inject("serve_decode", "resource_exhausted",
                           after=4, every=5, times=3), \
                _chaos.inject("serve_decode", "raise", after=12,
                              times=1):
            ids = []
            for p in prompts:
                while True:
                    try:
                        ids.append(router.submit(p,
                                                 sampling=sampling))
                        break
                    except EngineOverloaded:
                        sheds += 1      # shed-then-retry
                        time.sleep(0.05)
            router.wait(ids, timeout_s=600)
            storm_dt = time.perf_counter() - t0
            storm_gaps, storm_total = [], 0
            for i in ids:
                req = router.get_request(i)
                ts = req.token_times
                storm_gaps.extend(b - a for a, b in zip(ts, ts[1:]))
                storm_total += len(req.output_ids)
                router.release(i)
        assert not router.check_drained(), \
            "resilience twin leaked KV blocks"
    finally:
        router.shutdown()
    deltas = {k: _cmon.stat_get(k) - base[k] for k in keys}
    storm_tps = storm_total / storm_dt if storm_dt else 0.0
    r["resilience"] = {
        "storm_tokens_s": round(storm_tps, 1),
        "goodput_vs_clean": (round(storm_tps / cb_tps, 3)
                             if cb_tps else 0.0),
        **_itl_ms(storm_gaps),
        "sheds": sheds,
        "shed_rate": round(sheds / max(1, sheds + len(ids)), 4),
        "failovers": deltas["serve/failovers"],
        "counters": deltas,
        "storm": ("serve_decode:resource_exhausted:after=4:every=5:"
                  "times=3;serve_decode:raise:after=12:times=1"),
    }
    return r


def bench_linalg(on_tpu):
    """ISSUE 12: the distributed linear-algebra tier — SUMMA matmul
    GFLOP/s on the full device grid plus Cholesky/TSQR wall times,
    each against the single-device jnp.linalg reference. The
    comm/linalg counters land in extra.linalg via main()'s snapshot,
    and the twin timings say whether distribution paid for itself at
    this size (on the CPU smoke it usually cannot — the number is a
    trajectory anchor, not a win claim)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import build_mesh, get_mesh, set_mesh
    from paddle_tpu.linalg import dist as dla

    from paddle_tpu.core import monitor as _cmon

    n_dev = len(jax.devices())
    size = 2048 if on_tpu else 256
    prev = get_mesh()
    axes = ({"dp": 2, "mp": -1} if n_dev >= 4
            else {"dp": max(n_dev, 1)})
    set_mesh(build_mesh(axes))
    # the comm counters are process-cumulative and earlier configs
    # (ernie's hybrid compiler, serving) also move them — snapshot a
    # DELTA around this config so extra.linalg attributes only the
    # linalg algorithms' own collective traffic
    _comm_keys = ("comm/broadcast/bytes", "comm/broadcast/calls",
                  "comm/all_gather/bytes", "comm/all_gather/calls",
                  "comm/all_reduce/bytes", "comm/all_reduce/calls")
    comm0 = {k: _cmon.stat_get(k) for k in _comm_keys}
    try:
        rng = np.random.RandomState(0)
        a = rng.standard_normal((size, size)).astype(np.float32)
        m0 = rng.standard_normal((size, size)).astype(np.float32)
        spd = (m0 @ m0.T + size * np.eye(size)).astype(np.float32)
        tall = rng.standard_normal((size * 8, 32)).astype(np.float32)

        def timed(fn, iters=3):
            # block on the warmup: async dispatch would otherwise
            # bleed the warmup's device time into the timed window
            # (the CostModel.profile_measure discipline)
            jax.block_until_ready(fn())
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn()
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / iters

        A, B = dla.shard(a), dla.shard(a)
        dt_mm = timed(lambda: dla.matmul(A, B).value)
        gflops = 2 * size ** 3 / dt_mm / 1e9
        S = dla.shard(spd)
        dt_chol = timed(lambda: dla.cholesky(S).value)
        Tq = dla.shard(tall, layout="rows")
        dt_qr = timed(lambda: dla.qr(Tq)[0].value)
        # single-device references (same shapes, plain jnp on dev 0)
        dev = jax.devices()[0]
        aj = jax.device_put(a, dev)
        sj = jax.device_put(spd, dev)
        tj = jax.device_put(tall, dev)
        ref_mm = timed(lambda: jnp.matmul(aj, aj))
        ref_chol = timed(lambda: jnp.linalg.cholesky(sj))
        ref_qr = timed(lambda: jnp.linalg.qr(tj))
        r = _pack(round(gflops, 2), "summa_gflops", [dt_mm])
        r["size"] = size
        r["grid"] = repr(dla.grid())
        r["cholesky_ms"] = round(dt_chol * 1e3, 3)
        r["tsqr_ms"] = round(dt_qr * 1e3, 3)
        r["ref_matmul_ms"] = round(ref_mm * 1e3, 3)
        r["ref_cholesky_ms"] = round(ref_chol * 1e3, 3)
        r["ref_qr_ms"] = round(ref_qr * 1e3, 3)
        r["dist_vs_ref_matmul"] = (round(ref_mm / dt_mm, 4)
                                   if dt_mm else 0.0)
        r["comm"] = {k: _cmon.stat_get(k) - comm0[k]
                     for k in _comm_keys}
        return r
    finally:
        set_mesh(prev)
        dla.clear_program_cache()


def bench_qcomm(on_tpu):
    """ISSUE 14: the quantized-collective twin — the SAME dp training
    run through the explicit fp32 allreduce island and the int8
    error-feedback one (distributed.compress). Records the measured
    wire-bytes ratio (comm/all_reduce/wire_bytes deltas — the
    compression is priced, not asserted), the step-time delta (on
    the CPU smoke the quantize arithmetic usually COSTS time; the
    wire win needs real ICI), and the final-loss delta (the quality
    tax). Embedded as extra.qcomm by main()."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.core import monitor as _cmon
    from paddle_tpu.distributed import build_mesh, get_mesh, set_mesh
    from paddle_tpu.jit.distributed import DistributedTrainStepCompiler

    n_dev = len(jax.devices())
    steps = 24 if on_tpu else 12
    hidden = 2048 if on_tpu else 256
    prev = get_mesh()
    keys = ("comm/all_reduce/bytes", "comm/all_reduce/wire_bytes")

    rng = np.random.RandomState(0)
    xs = [rng.randn(2 * n_dev, 64).astype(np.float32)
          for _ in range(steps)]
    ys = [rng.randn(2 * n_dev, 8).astype(np.float32)
          for _ in range(steps)]

    def run(spec):
        paddle.seed(0)
        mesh = build_mesh({"dp": n_dev})
        set_mesh(mesh)
        model = nn.Sequential(nn.Linear(64, hidden), nn.ReLU(),
                              nn.Linear(hidden, 8))
        opt = optim.AdamW(learning_rate=1e-2,
                          parameters=model.parameters())
        step = DistributedTrainStepCompiler(
            model, opt, loss_fn=lambda o, t: ((o - t) ** 2).mean(),
            mesh=mesh, comm_compress=spec)
        c0 = {k: _cmon.stat_get(k) for k in keys}
        loss = step(paddle.to_tensor(xs[0]),
                    paddle.to_tensor(ys[0]))  # compile + step 0
        losses = [float(loss.item())]
        t0 = time.perf_counter()
        for x, y in zip(xs[1:], ys[1:]):
            loss = step(paddle.to_tensor(x), paddle.to_tensor(y))
        losses.append(float(loss.item()))
        dt = (time.perf_counter() - t0) / (steps - 1)
        return {"first_loss": round(losses[0], 6),
                "final_loss": round(losses[-1], 6),
                "step_ms": round(dt * 1e3, 3),
                "comm": {k: _cmon.stat_get(k) - c0[k] for k in keys}}

    try:
        fp32 = run("fp32")
        int8 = run("int8:ef")
        ratio = (int8["comm"]["comm/all_reduce/wire_bytes"]
                 / max(fp32["comm"]["comm/all_reduce/wire_bytes"], 1))
        r = _pack(round(ratio, 4), "wire_bytes_ratio",
                  [int8["step_ms"] / 1e3])
        r["devices"] = n_dev
        r["fp32"] = fp32
        r["int8_ef"] = int8
        r["step_time_delta_ms"] = round(
            int8["step_ms"] - fp32["step_ms"], 3)
        r["final_loss_delta"] = round(
            abs(int8["final_loss"] - fp32["final_loss"]), 6)
        return r
    finally:
        set_mesh(prev)


def main(argv=None):
    import jax

    argv = list(sys.argv[1:] if argv is None else argv)
    baseline = "--baseline" in argv
    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    suite = {
        "mnist_lenet": bench_mnist,
        "resnet50": bench_resnet50,
        "resnet50_pipeline": bench_resnet50_pipeline,
        "bert_base": bench_bert,
        "gpt2_345m": bench_gpt2,
        "ernie": bench_ernie,
        "serving": bench_serving,
        "linalg": bench_linalg,
        "qcomm": bench_qcomm,
    }
    results = {}
    failed = []
    for name, fn in suite.items():
        try:
            r = fn(on_tpu)
            # configs without a published stand-in (serving) record 0
            r["vs_baseline"] = (round(r["value"] / BASELINES[name], 4)
                                if on_tpu and name in BASELINES
                                else 0.0)
            results[name] = r
            print(f"[bench] {name}: {r['value']} {r['unit']} "
                  f"(vs_baseline {r['vs_baseline']})", file=sys.stderr)
        except Exception as e:  # record, don't lose the other configs
            failed.append(name)
            results[name] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] {name} FAILED: {e}", file=sys.stderr)

    # full telemetry trail of the run (jit compile counters, comm
    # bytes, io + step stats) — the StatRegistry snapshot the monitor
    # exporter would flush, embedded so every bench record carries it
    try:
        from paddle_tpu import monitor as _monitor

        results["telemetry"] = _monitor.telemetry_snapshot()
        # lint-cleanliness of the run, called out separately from the
        # full snapshot: analysis/<code>/findings counters say whether
        # the benchmarked programs tripped any PTA diagnostics (ISSUE
        # 2), so the perf trajectory records clean-vs-dirty runs
        results["analysis"] = {
            k: v for k, v in results["telemetry"]["stats"].items()
            if k.startswith("analysis/")}
        # failure-forensics health, called out like analysis/: ring
        # drops, watchdog fires and dump bundles written during the
        # bench say whether the run was clean or left evidence behind
        # (ISSUE 3)
        results["flight"] = {
            k: v for k, v in results["telemetry"]["stats"].items()
            if k.startswith("flight/")}
        # latency-hiding pipeline attribution (ISSUE 4): how many XLA
        # dispatches covered how many train steps, and what the device
        # prefetcher moved/hid — the counters that say WHERE a
        # throughput delta came from
        results["pipeline"] = {
            k: v for k, v in results["telemetry"]["stats"].items()
            if k.startswith("io/device_prefetch/")
            or k in ("io/h2d_us", "jit/dispatches", "jit/steps",
                     "jit/steps_per_dispatch")}
        # memory trajectory (ISSUE 5): device allocated/peak gauges,
        # per-program HBM footprints (mem/program/<fn>/*) and the
        # step-boundary gauges — BENCH_r06+ records track peak-HBM
        # alongside throughput so a perf win that costs memory
        # headroom is visible in the same record
        results["memory"] = {
            k: v for k, v in results["telemetry"]["stats"].items()
            if k.startswith(("mem/", "step/mem/"))}
        # elastic-checkpointing robustness tax (ISSUE 6): writer
        # throughput/drops during the bench plus the measured
        # step-time overhead (mnist ckpt_overhead_pct) — BENCH_r06+
        # tracks what fault tolerance costs alongside what perf wins
        results["ckpt"] = {
            k: v for k, v in results["telemetry"]["stats"].items()
            if k.startswith("ckpt/")}
        # chaos/resilience provenance (ISSUE 7): chaos/* proves the
        # run was fault-free (or names exactly what was injected),
        # and comm/retries + train/nonfinite_* + io/workers/* +
        # io/bad_samples + amp/scale/* record what the self-healing
        # layers absorbed — a perf number with hidden retries or
        # skipped steps is not a clean perf number
        results["resilience"] = {
            k: v for k, v in results["telemetry"]["stats"].items()
            if k.startswith(("chaos/", "io/workers/", "amp/scale/"))
            or k in ("comm/retries", "io/bad_samples",
                     "train/nonfinite_skips",
                     "train/nonfinite_stops")}
        # MFU campaign provenance (ISSUE 8): this run's TOTAL compile
        # time and JAX's persistent compilation cache (where it
        # lives, whether this run armed it, its requests/hits) — a
        # second run against a warm cache shows hits > 0 and a
        # measurably lower total_compile_us; pallas_fusion records
        # whether the fused kernel library was armed for these
        # numbers, so fused and unfused records can't be confused in
        # the trajectory
        from paddle_tpu.jit import persistent_cache as _pcache

        stats = results["telemetry"]["stats"]
        try:
            from paddle_tpu.incubate.nn import pallas as _pallas

            fusion = _pallas.fusion_enabled()
        except Exception:
            fusion = False
        results["compile"] = {
            "total_compile_us": sum(
                v for k, v in stats.items()
                if k.endswith("/compile_us")),
            "native_cache": _pcache.native_cache_stats(),
            "pallas_fusion": fusion,
        }
        # runtime sanitizer provenance (ISSUE 10): which PADDLE_SANITIZE
        # families were armed for this run plus every sanitize/*,
        # numerics/* (the PTA09x probe gauges) and PTA04x-09x
        # findings counter
        from paddle_tpu.monitor import sanitize as _sanitize

        results["sanitize"] = {
            "armed": _sanitize.families(),
            "counters": {
                k: v for k, v in stats.items()
                if k.startswith(("sanitize/", "numerics/",
                                 "analysis/PTA04",
                                 "analysis/PTA05", "analysis/PTA06",
                                 "analysis/PTA07",
                                 "analysis/PTA08",
                                 "analysis/PTA09"))}}
        # SLO alert provenance (ISSUE 20): which PADDLE_ALERTS rules
        # were armed for this run, each rule's terminal state, and
        # every alerts/* + serve/autoscale/* counter — a bench round
        # that burned its SLOs (or silently grew replicas) names it
        from paddle_tpu.monitor import alerts as _alerts

        results["alerts"] = {
            "armed": [r.name for r in _alerts.rules()],
            "rules": [r.describe() for r in _alerts.rules()],
            "counters": {
                k: v for k, v in stats.items()
                if k.startswith(("alerts/", "serve/autoscale/"))}}
        # serving-engine attribution (ISSUE 11): request/token
        # volumes, prefill vs decode wall time, KV-pool occupancy
        # and the eviction counts behind the serving config's
        # tokens/s — a throughput number that hid pool thrash or
        # admission starvation is not a clean number
        results["serve"] = {
            k: v for k, v in stats.items()
            if k.startswith("serve/")}
        # serving-resilience twin (ISSUE 13): the serving config's
        # goodput-under-chaos record — tokens/s + p50/p99 ITL with a
        # serve_decode fault storm (OOM churn + a replica kill)
        # armed, shed rate and failover count, vs the clean
        # continuous-batching number. A serving perf record that
        # never names its failure behavior under load is only half a
        # record (the 2605.25645 tail-behavior argument)
        srv = results.get("serving")
        if isinstance(srv, dict) and "resilience" in srv:
            results["serve_resilience"] = srv.pop("resilience")
        # speculative-decoding + prefix-cache twin (ISSUE 19): the
        # serving config's steady-state record with spec_k=4 drafting
        # + copy-on-write prefix sharing armed — tokens/s and p50/p99
        # ITL vs the k=1/no-cache baseline on the same request set,
        # acceptance rate and prefill-tokens-saved. A gateable config
        # of its own: regress.py picks extra.serve_spec.value up off
        # the trail automatically
        if isinstance(srv, dict) and "spec" in srv:
            results["serve_spec"] = srv.pop("spec")
        # tail-latency trajectories (ISSUE 15): the serving
        # histograms' full bucket summaries + p50/p95/p99 (ms), so
        # BENCH rounds carry latency DISTRIBUTIONS, not just
        # throughput — the serving and resilience configs above both
        # fed these (TTFT, inter-token, queue-wait, e2e)
        from paddle_tpu.core.monitor import snapshot_quantile

        results["latency"] = {
            name: {
                "count": snap["count"],
                "p50_ms": round(
                    snapshot_quantile(snap, 0.5) / 1e3, 3),
                "p95_ms": round(
                    snapshot_quantile(snap, 0.95) / 1e3, 3),
                "p99_ms": round(
                    snapshot_quantile(snap, 0.99) / 1e3, 3),
                "hist": snap,
            }
            for name, snap in (results["telemetry"].get("hists")
                               or {}).items()
            if name.startswith("serve/hist/")}
        # distributed-linalg attribution (ISSUE 12): program counts
        # and bytes processed behind the linalg config's GFLOP/s.
        # linalg/* counters only the dist tier produces; the comm
        # volume (which other configs also move) is recorded as a
        # per-config DELTA inside bench_linalg's own record
        # (results['linalg']['comm']) — the collective traffic is
        # the algorithm, so a perf record without it is
        # unexplainable. Keyed linalg_counters: results['linalg'] is
        # the config record itself
        results["linalg_counters"] = {
            k: v for k, v in stats.items()
            if k.startswith("linalg/")}
        # compute attribution (ISSUE 16): the roofline ledger behind
        # every MFU column — per-program compiler-reported FLOPs/bytes
        # (perf/program/*), measured dispatch quantiles, achieved
        # FLOP/s, per-program MFU against the SAME peak table the
        # config MFU columns use, and the roofline verdict. Plus
        # analytic-vs-compiler FLOPs drift per config: the published
        # formulas the MFU columns are built on, sanity-checked
        # against what XLA says the program actually executes — a
        # drifting ratio means the MFU trajectory is mispriced
        from paddle_tpu.monitor import perf as _perf

        perf_rep = _perf.perf_report()
        drift = {}
        for cname, rec in results.items():
            if not isinstance(rec, dict) or "program" not in rec:
                continue
            prog = rec["program"]
            an = rec.get("analytic_flops_per_step")
            comp = (perf_rep["programs"].get(prog) or {}).get("flops")
            drift[cname] = {
                "program": prog,
                "analytic_flops": an,
                "compiler_flops": comp,
                "ratio": (round(an / comp, 4)
                          if an and comp else None)}
        results["perf"] = {
            "enabled": _perf.program_capture_enabled(),
            "peaks": perf_rep["peaks"],
            "programs": perf_rep["programs"],
            "flops_drift": drift,
            "gauges": {k: v for k, v in stats.items()
                       if k.startswith(("perf/", "step/attrib/"))},
        }
    except Exception as e:
        results["telemetry"] = {"error": f"{type(e).__name__}: {e}"}
    # zero-overhead contract, asserted OUTSIDE the telemetry
    # try/except so a regression actually fails the bench: like the
    # chaos `_armed` gate, disarmed sanitizers must leave NO counters
    # behind. Scoped to the counters only ARMED runtime hooks create:
    # sanitize/spec_errors records a rejected (ignored) spec, and the
    # analysis/PTA0xx findings counters are also fed by the
    # report-only static passes under PADDLE_ANALYSIS=1 — neither is
    # runtime-sanitizer overhead
    san_extra = results.get("sanitize")
    if san_extra is not None and not san_extra["armed"]:
        leaked = {k: v for k, v in san_extra["counters"].items()
                  if k.startswith(("sanitize/", "numerics/"))
                  and k != "sanitize/spec_errors"}
        assert not leaked, (
            "disarmed sanitizers left counters behind "
            f"(zero-overhead contract broken): {leaked}")
    # same contract for the alert plane (ISSUE 20): with
    # PADDLE_ALERTS unset there is no evaluator thread and no
    # autoscaler listener, so EVERY alerts/* and serve/autoscale/*
    # counter must be exactly absent (alerts/spec_errors records a
    # rejected spec — loudness, not armed overhead)
    al_extra = results.get("alerts")
    if al_extra is not None and not al_extra["armed"]:
        leaked = {k: v for k, v in al_extra["counters"].items()
                  if k != "alerts/spec_errors"}
        assert not leaked, (
            "disarmed alert/autoscale plane left counters behind "
            f"(zero-overhead contract broken): {leaked}")
    # same contract for the perf plane: PADDLE_PERF_PROGRAM=0 must
    # leave the perf/program/* ledger empty — a disarmed opt-out that
    # still pays capture compiles (or writes gauges) is not an opt-out
    perf_extra = results.get("perf")
    if isinstance(perf_extra, dict) and not perf_extra["enabled"]:
        leaked = {k: v for k, v in perf_extra["gauges"].items()
                  if k.startswith("perf/") and v}
        assert not leaked, (
            "PADDLE_PERF_PROGRAM=0 left perf gauges behind "
            f"(zero-overhead contract broken): {leaked}")

    flag = results.get("gpt2_345m", {})
    out = {
        "metric": ("gpt2_345m_train_tokens_per_sec_per_chip" if on_tpu
                   else "gpt2_tiny_cpu_smoke_tokens_per_sec"),
        "value": flag.get("value", 0.0),
        "unit": flag.get("unit", "tokens/s"),
        "vs_baseline": flag.get("vs_baseline", 0.0),
        "extra": results,
    }
    print(json.dumps(out))
    if failed:
        print(f"[bench] configs that raised: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    if baseline:
        # regression gate (ISSUE 16): compare THIS run against the
        # newest BENCH_r*.json trail round with window_spread-derived
        # noise bands; nonzero rc fails the bench invocation
        import tempfile

        bench_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmarks")
        if bench_dir not in sys.path:
            sys.path.insert(0, bench_dir)
        import regress

        with tempfile.NamedTemporaryFile(
                "w", suffix=".json", prefix="bench_baseline_",
                delete=False) as f:
            json.dump(out, f)
            cur_path = f.name
        try:
            return regress.main(["--current", cur_path])
        finally:
            os.unlink(cur_path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
