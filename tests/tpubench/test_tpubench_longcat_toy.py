"""A toy `longcat_flash` configuration (a chip's share: 4 of 16 experts, the
router 24 wide) run end to end by the `serve_offline` kind on the CPU: the
family module builds the engine the harness steps, the check teacher-forces
the plain reference given the same share, and the five `.longcat` metrics
(data files over the program's `serve/moe/*` counters) read a value."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy_tree  # noqa: E402

CELL = "toy-longcat-offline"
ROUTING = ("moe_zero_choice_share.longcat",
           "moe_held_assignment_share.longcat",
           "moe_experts_hit_share.longcat",
           "moe_max_load_over_mean.longcat")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("bench_longcat"))
    before = toy_tree.build(dst)
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-longcat", "source": "none",
        "file": "tpubench/configs/toy-longcat-flash.json", "reduced": [],
        "why": "toy width for the CPU tests"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-longcat", "traffic": "toy-offline",
        "chips": 1, "why": "CPU test of the harness"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("serve_tok_s", "decode_roofline.longcat") + ROUTING:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return dst, before


def _run(dst, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": toy_tree.REPO}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "tpubench/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 31), "--seconds", "0.5", "--trace", str(trace)],
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


def test_the_share_metrics_read_a_value(tree):
    dst, _ = tree
    line, _ = _run(dst, 1)
    assert line["correct"] is True
    zero, held, hit, load = (line["metrics"][m]["value"] for m in ROUTING)
    # 8 of the router's 24 outputs are zero-compute and 4 are held here;
    # the scales are written for 16 held experts
    assert 10 < zero < 60
    assert 0 < held < 100 - zero
    assert 0 < hit <= 100 * 4 / 16
    assert load >= 4.0
    # the device's share of a roofline is not read on the CPU
    assert "decode_roofline.longcat" not in line["metrics"]


def test_the_cells_shape_counts():
    """The configuration file's arithmetic, from the family's functions."""
    sys.path.insert(0, toy_tree.REPO)
    try:
        from tpubench.models import longcat_flash as fam
    finally:
        sys.path.remove(toy_tree.REPO)
    with open(os.path.join(toy_tree.REPO, "tpubench", "configs",
                           "longcat-flash-serve.json")) as f:
        config = json.load(f)
    assert fam.param_count(config) == 5_172_749_312
    assert fam.latent_row(config) == 576
    # a step at 2.7k tokens a sequence that hits 10 of 16 experts a layer
    # with 16 assignments: bytes-bound, about 10 GB
    flops, nbytes = fam.decode_least(config, 64 * 2700, 64, 40, 64, 2)
    assert 9.5e9 < nbytes < 10.5e9
    assert flops / 197e12 < nbytes / 819e9
