"""A toy `glm4_moe_lite` configuration run end to end by the `serve_offline`
kind on the CPU: the family module builds the engine the harness steps, the
check teacher-forces the plain reference, and the routing metrics (data files
over the program's `serve/moe/*` counters) read a value."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy_tree  # noqa: E402

CELL = "toy-glm-offline"
ROUTING = ("moe_experts_hit_share.glm47f", "moe_max_load_over_mean.glm47f")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("bench_glm"))
    before = toy_tree.build(dst)
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-glm", "source": "none",
        "file": "tpubench/configs/toy-glm4-moe-lite.json", "reduced": [],
        "why": "toy width for the CPU tests"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-glm", "traffic": "toy-offline",
        "chips": 1, "why": "CPU test of the harness"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("serve_tok_s", "decode_roofline.glm47f") + ROUTING:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return dst, before


def _run(dst, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": toy_tree.REPO}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "tpubench/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 27), "--seconds", "0.5", "--trace", str(trace)],
        cwd=dst, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return json.loads(p.stdout.splitlines()[-1]), p.stdout


def test_the_toy_cell_runs_and_is_correct(tree):
    dst, before = tree
    line, out = _run(dst, 0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert "0 XLA lowerings" in out and "0 jit cache misses" in out
    assert toy_tree.unchanged(dst, before)


def test_the_routing_metrics_read_a_value(tree):
    dst, _ = tree
    line, _ = _run(dst, 1)
    assert line["correct"] is True
    hit = line["metrics"]["moe_experts_hit_share.glm47f"]["value"]
    load = line["metrics"]["moe_max_load_over_mean.glm47f"]["value"]
    # 8 experts under a scale written for 64: at most 8 of "64" hit, and
    # the fullest expert holds at least the mean
    assert 0 < hit <= 100 * 8 / 64
    assert load >= 8.0
    # the device's share of a roofline is not read on the CPU
    assert "decode_roofline.glm47f" not in line["metrics"]
