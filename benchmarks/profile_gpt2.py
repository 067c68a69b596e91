"""GPT-2 345M step profile capture (r3 weak #2: "no profile artifact"
— this script records one with the repo's own merged-timeline
profiler).

Captures a few bench-config GPT-2 train steps under
paddle_tpu.profiler.Profiler (host RecordEvents + jax/XLA device trace
folded into ONE chrome trace), writes the trace next to this script,
and prints a JSON summary of where the non-GEMM time goes — the
evidence behind the K-geometry ceiling argument (gemm_probe.py gives
the GEMM side).

Usage: python benchmarks/profile_gpt2.py [--steps 3]
Output: benchmarks/artifacts/gpt2_step_trace.json (chrome://tracing /
perfetto loadable) + one JSON summary line on stdout.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root, for paddle_tpu

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", default="/tmp/gpt2_step_trace.json",
                    help="full chrome trace (large — not committed)")
    ap.add_argument("--summary", default=os.path.join(
        os.path.dirname(__file__), "artifacts",
        "gpt2_step_summary.json"))
    args = ap.parse_args()

    import jax
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.optimizer as optim
    import paddle_tpu.profiler as profiler
    from paddle_tpu.jit import TrainStepCompiler
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    paddle.seed(0)
    if on_tpu:  # the bench.py gpt2_345m config, verbatim
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024,
                        num_layers=24, num_heads=16, ffn_hidden=4096,
                        max_seq_len=1024, dropout=0.0, remat=False,
                        use_flash_attention=True, scan_unroll=24)
        batch, seq = 4, 1024
    else:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, ffn_hidden=256, max_seq_len=128,
                        dropout=0.0, remat=False,
                        use_flash_attention=False)
        batch, seq = 4, 128
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model = amp.decorate(model, level="O2", dtype="bfloat16")
    opt = optim.AdamW(learning_rate=1e-4,
                      parameters=model.parameters(),
                      weight_decay=0.01, multi_precision=on_tpu)
    step = TrainStepCompiler(model, opt, loss_fn=None)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                       (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                          (batch, seq)).astype(np.int32))
    step(ids, labels).item()  # compile outside the trace

    prof = profiler.Profiler(python_tracer=False)
    prof.start()
    for _ in range(args.steps):
        with profiler.RecordEvent("train_step"):
            loss = step(ids, labels)
        loss.item()
        prof.step()
    prof.stop()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    prof.export(args.out)

    # summarize the DEVICE timeline. The merged export folds several
    # profiler planes in as pid>=1000; one of them is jax's host
    # python-frame plane. Classify planes by content: a DEVICE plane
    # is one where most duration sits in XLA-op-shaped names
    # (while/fusion/convolution/jit_.../closed_call/...).
    import re

    with open(args.out) as f:
        events = json.load(f)["traceEvents"]
    xla_re = re.compile(
        r"^(while|fusion|copy|dot|conv|bitcast|add|mult|sub|div|"
        r"reduce|broadcast|transpose|dynamic|closed_call|call|jit_|"
        r"scatter|gather|select|compare|tuple|param|slice|concat|"
        r"rsqrt|exp|log|custom-call|all-|collective|iota|pad|rng|"
        r"cholesky|sort|convert|negate|power|maximum|minimum|tanh)")
    by_pid = collections.defaultdict(list)
    for e in events:
        if e.get("pid", 0) >= 1000 and e.get("dur", 0) > 0:
            by_pid[e["pid"]].append(e)
    device_events = []
    for pid, evs in by_pid.items():
        tot = sum(e["dur"] for e in evs)
        xla = sum(e["dur"] for e in evs
                  if xla_re.match(e["name"].lower()))
        if tot > 0 and xla / tot > 0.5:
            device_events.extend(evs)

    envelope_us = sum(e["dur"] for e in device_events
                      if e.get("name", "").startswith("jit_"))
    op_events = [e for e in device_events
                 if not e["name"].isdigit()          # thread-lane rows
                 and not e["name"].startswith("jit_")]  # step envelope
    bucket = collections.Counter()
    top_ops = collections.Counter()
    for e in op_events:
        name = e["name"]
        low = name.lower()
        top_ops[name.split("(")[0][:48]] += e["dur"]
        if low.startswith("while"):
            # the transformer layer stack is a lax.scan — fwd and bwd
            # each lower to one while op; per-layer ops live inside
            bucket["layer-scan (fwd+bwd bodies)"] += e["dur"]
        elif any(t in low for t in ("dot", "matmul", "gemm", "conv",
                                    "einsum")):
            bucket["gemm/conv"] += e["dur"]
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
    summary = {
        "trace": args.out,
        "steps": args.steps,
        "per_step_device_ms": round(envelope_us / 1e3 / args.steps, 2)
        if envelope_us else None,
        "opcount_device": len(op_events),
        "breakdown_pct": {k: round(100.0 * v / total, 1)
                          for k, v in bucket.most_common()},
        "top_ops_ms": {k: round(v / 1e3, 2)
                       for k, v in top_ops.most_common(15)},
        "note": "open the full trace in perfetto for the merged "
                "host+device timeline",
    }
    os.makedirs(os.path.dirname(args.summary), exist_ok=True)
    with open(args.summary, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
