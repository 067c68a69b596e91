"""A toy `mellum` configuration (two periods S S S F, window 8, block 4, 8
experts top 2, YaRN over 16 original positions x 4) run end to end by the
`serve_offline` kind on the CPU: the family module builds the engine the
harness steps (prompts of 16 and 32 tokens, two and four windows, decode to
five and a half), the check teacher-forces the plain reference, and the
`.mellum2` metrics (data files over readers) read a value or, where they
need the chip's trace, nothing; and the real configuration's arithmetic."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy_tree  # noqa: E402

CELL = "toy-mellum-offline"
REAL = "serve-mellum2-offline-decode-8k"
COUNTERS = ("moe_experts_hit_share.mellum2", "moe_max_load_over_mean.mellum2",
            "kv_window_blocks_over_least.mellum2", "kv_window_share.mellum2")
TRACE = ("decode_roofline.mellum2", "kernel_roofline.mellum2",
         "attend_roofline.mellum2")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("bench_mellum"))
    before = toy_tree.build(dst)
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-mellum", "source": "none",
        "file": "tpubench/configs/toy-mellum.json", "reduced": [],
        "why": "toy width for the CPU tests"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-mellum", "traffic": "toy-offline",
        "chips": 1, "why": "CPU test of the harness"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return dst, before


def _run(dst, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": toy_tree.REPO}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "tpubench/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 38), "--seconds", "0.5", "--trace", str(trace)],
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
    hit, load, over_least, share = (line["metrics"][m]["value"]
                                    for m in COUNTERS)
    # 8 experts under a scale written for 64
    assert 0 < hit <= 100 * 8 / 64 and load >= 8.0
    # a window of 8 in blocks of 4: 2 or 3 blocks hold what a query sees,
    # at most 4 are held; three window groups beside 5-11 blocks of the
    # full group
    assert 1.0 <= over_least <= 4 / 2
    assert 100 * 6 / (6 + 11) <= share <= 100 * 12 / (12 + 5)
    assert line["metrics"]["paged_attention_step_share.offline"]["value"] == 0
    # the device's shares of a roofline are not read on the CPU
    assert not set(TRACE) & set(line["metrics"])


@pytest.fixture(scope="module")
def full():
    sys.path.insert(0, toy_tree.REPO)
    try:
        from tpubench.models import mellum as fam
    finally:
        sys.path.remove(toy_tree.REPO)
    with open(os.path.join(toy_tree.REPO, "tpubench", "configs",
                           "mellum2-12b-a2.5b-serve.json")) as f:
        return fam, json.load(f)


def test_the_cells_shape_counts(full):
    """The configuration file's arithmetic, from the family's functions."""
    fam, config = full
    assert fam.layer_types(config) == (
        "sliding_attention",) * 3 + ("full_attention",) + (
        "sliding_attention",) * 3 + ("full_attention",)
    assert fam.param_count(config) == 3_794_968_832
    s = config["serve"]
    # 640 blocks of the full group and 66 of each of three window groups
    # at the longest context, where every layer kept whole holds 2560
    assert fam.cache_blocks_per_seq(config, config["n_positions"]) == 838
    assert s["num_blocks"] == s["max_batch"] * 838 + 1
    assert 2 * 16 * 512 * 2 * 2 == 64 * 1024          # a block's bytes
    # a step of 96 sequences at 8k tokens that hits every expert:
    # bytes-bound, 11.5 GB, of which the custom calls' least is 10.7 and
    # the attention's 3.6 (2 layers x 8000 rows + 6 x 1024, 2 KiB a row)
    ctx = 96 * 8000
    flops, nbytes = fam.decode_least(config, ctx, 96, 8 * 64, 2)
    assert 11.3e9 < nbytes < 11.7e9
    assert flops / 197e12 < nbytes / 819e9
    k_flops, k_bytes = fam.kernels_least(config, ctx, 96, 8 * 64, 2)
    a_flops, a_bytes = fam.attend_least(config, ctx, 96, 2)
    assert a_bytes == 96 * (2 * 8000 + 6 * 1024) * 2048
    assert a_bytes < k_bytes < nbytes and a_flops < k_flops < flops
    # under the window every layer reads what there is
    assert fam.kv_row_tokens(config, 96 * 500, 96) == 8 * 96 * 500


def test_the_file_keeps_the_catalogs_numbers(full):
    """Every key of the published config is in the file as published, but
    the depth; the cut is of depth alone and names no width."""
    _, config = full
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    assert {k: config[k] for k in published} == published
    assert len(config["layer_types"]) == 28 == config["published"][
        "num_hidden_layers"] == len(config["mlp_layer_types"])
    assert set(config["mlp_layer_types"]) == {"sparse"}
    assert config["reduced"] == ["num_hidden_layers"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert all(isinstance(v, str) and v for v in config["assumed"].values())
    s = config["serve"]
    assert 0 < s["logit_mean_margin"] < s["logit_margin"]
    assert len(s["logit_margin_why"]) > 200
    assert config["n_positions"] == 8192 + 2048
