"""A configuration, traffic mixes, cells and per-layer metrics added to a
temporary copy of the benchmark as files and entries only, and the cells run
by the one command, on the CPU at toy size. A toy configuration names itself
(`cpu_toy`), prints `cpu` as its device and no device metric."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy_tree  # noqa: E402


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dst = tmp_path_factory.mktemp("bench")
    before = toy_tree.build(str(dst))
    return str(dst), before


def _run(tree, workload, trace, seconds, seed=2 ** 31 + 11):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": toy_tree.REPO}
    env.pop("XLA_FLAGS", None)
    if workload == "toy-dp4":       # four virtual devices stand for the host
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    p = subprocess.run(
        [sys.executable, "tpubench/run.py", "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=tree, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return json.loads(p.stdout.splitlines()[-1]), p.stdout


@pytest.mark.parametrize("workload,e2e,layer_metric,seconds", [
    ("toy-train", "train_tok_s_chip", "toy_steps", 2),
    ("toy-dp4", "train_tok_s_chip", "toy_steps", 2),
    ("toy-offline", "serve_tok_s", "toy_tokens_emitted", 0.5),
])
def test_a_cell_added_as_files_runs(tree, workload, e2e, layer_metric,
                                    seconds):
    dst, before = tree
    line, out = _run(dst, workload, 0, seconds)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {e2e, "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert "0 XLA lowerings" in out and "0 jit cache misses" in out

    traced, _ = _run(dst, workload, 1, seconds)
    # the per-layer metric that was added as data, and no device metric
    assert traced["metrics"][layer_metric]["value"] > 0
    assert not [m for m in traced["metrics"]
                if "roofline" in m or "idle" in m or "hbm" in m
                or "flops" in m]
    assert "busy_s" not in traced["device"] and "breakdown" not in traced
    assert toy_tree.unchanged(dst, before)
    logs = os.listdir(os.path.join(
        dst, "tpubench_out", workload, f"seed{2 ** 31 + 11}-trace0"))
    assert "steps.jsonl" in logs
