"""A toy `lfm2_moe` configuration (7 layers: 5 short convolutions, 2 grouped
attentions of 8 query heads over 2 K/V heads, 5 expert layers) run end to end
by the `serve_offline` kind on the CPU: the family module builds the engine
the harness steps, the check teacher-forces the plain reference, and the
`.lfm2` metrics (data files over existing readers) read a value or, where
they need the chip's trace, nothing."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy_tree  # noqa: E402

CELL = "toy-lfm2-offline"
ROUTING = ("moe_experts_hit_share.lfm2", "moe_max_load_over_mean.lfm2")
TRACE = ("decode_roofline.lfm2", "kernel_roofline.lfm2")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("bench_lfm2"))
    before = toy_tree.build(dst)
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-lfm2", "source": "none",
        "file": "tpubench/configs/toy-lfm2-moe.json", "reduced": [],
        "why": "toy width for the CPU tests"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-lfm2", "traffic": "toy-offline",
        "chips": 1, "why": "CPU test of the harness"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("serve_tok_s",) + ROUTING + TRACE:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return dst, before


def _run(dst, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": toy_tree.REPO}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "tpubench/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 34), "--seconds", "0.5", "--trace", str(trace)],
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


def test_the_new_metrics_read_a_value_or_nothing(tree):
    dst, _ = tree
    line, _ = _run(dst, 1)
    assert line["correct"] is True
    hit, load = (line["metrics"][m]["value"] for m in ROUTING)
    # 8 experts under a scale written for 64: at most 8 of "64" hit, and
    # the fullest expert holds at least the mean
    assert 0 < hit <= 100 * 8 / 64
    assert load >= 8.0
    # the device's shares of a roofline are not read on the CPU
    assert not set(TRACE) & set(line["metrics"])


@pytest.fixture(scope="module")
def full():
    sys.path.insert(0, toy_tree.REPO)
    try:
        from tpubench.models import lfm2_moe as fam
    finally:
        sys.path.remove(toy_tree.REPO)
    with open(os.path.join(toy_tree.REPO, "tpubench", "configs",
                           "lfm2-24b-a2b-serve.json")) as f:
        return fam, json.load(f)


def test_the_cells_shape_counts(full):
    """The configuration file's arithmetic, from the family's functions."""
    fam, config = full
    assert fam.layer_types(config) == (
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv")
    assert fam.param_count(config) == 5_267_090_176
    assert fam.kv_bytes_per_token(config, 2) == 4096
    assert fam.state_bytes_per_seq(config, 2) == 64 * 1024
    s = config["serve"]
    assert s["num_blocks"] == s["max_batch"] * (
        config["n_positions"] // s["block_size"]) + 1
    # a step of 256 sequences at 2.4k tokens that hits every expert:
    # bytes-bound, about 13 GB, of which the custom calls' least is 12
    ctx = 256 * 2400
    flops, nbytes = fam.decode_least(config, ctx, 256, 8 * 64, 2)
    assert 12.5e9 < nbytes < 13.5e9
    assert flops / 197e12 < nbytes / 819e9
    k_flops, k_bytes = fam.kernels_least(config, ctx, 256, 8 * 64, 2)
    assert 11.5e9 < k_bytes < nbytes and k_flops < flops


def test_the_file_keeps_the_catalogs_numbers(full):
    """Every key of the published config is in the file as published, but
    the depth; the cut is of depth alone and names no width."""
    _, config = full
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert {k: config[k] for k in published} == published
    assert len(config["layer_types"]) == 40 == config["published"][
        "num_hidden_layers"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert all(isinstance(v, str) and v for v in config["assumed"].values())
    s = config["serve"]
    assert 0 < s["logit_mean_margin"] < s["logit_margin"]
    assert len(s["logit_margin_why"]) > 200
