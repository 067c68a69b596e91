"""A toy `falcon_h1` configuration (3 layers of attention, 10 query heads over
2 K/V heads, beside a Mamba-2 mixer of 4 heads over 2 groups) run end to end
by the `serve_offline` kind on the CPU: the family module builds the engine
the harness steps, the check teacher-forces the plain reference (and reads
two faults planted in the program as not correct), and the `.falconh1`
metrics (data files over readers) read a value or, where they need the
chip's trace, nothing; and the real configuration's arithmetic."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy_tree  # noqa: E402

CELL = "toy-falconh1-offline"
REAL = "serve-falconh1-offline-decode"
TRACE = ("decode_mfu.falconh1", "ssm_state_roofline.falconh1")

# a run of the harness with one fault planted in the program first
PLANT = """
import runpy, sys
import jax.numpy as jnp
from paddle_tpu.incubate.nn import ssm
from paddle_tpu.inference.serving import state_runner
fault = sys.argv.pop(1)
if fault == "other_slot":
    real = state_runner.prefill_step
    state_runner.prefill_step = lambda *a, **kw: real(
        *a[:-1], (a[-1] + 1) % 4, **kw)
elif fault == "state_not_carried":
    real = ssm.ssm_step
    ssm.ssm_step = lambda st, *a: real(jnp.zeros_like(st), *a)
sys.argv[0] = "tpubench/run.py"
runpy.run_path("tpubench/run.py", run_name="__main__")
"""


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("bench_falconh1"))
    before = toy_tree.build(dst)
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-falcon-h1", "source": "none",
        "file": "tpubench/configs/toy-falcon-h1.json", "reduced": [],
        "why": "toy width for the CPU tests"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-falcon-h1", "traffic": "toy-offline",
        "chips": 1, "why": "CPU test of the harness"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    with open(os.path.join(dst, "plant.py"), "w") as f:
        f.write(PLANT)
    return dst, before


def _run(dst, trace, fault=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": toy_tree.REPO}
    env.pop("XLA_FLAGS", None)
    env.pop("PADDLE_PALLAS_INTERPRET", None)
    cmd = ["tpubench/run.py"] if fault is None else ["plant.py", fault]
    p = subprocess.run(
        [sys.executable, *cmd, "--workload", CELL, "--seed",
         str(2 ** 31 + 41), "--seconds", "0.5", "--trace", str(trace)],
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
    # the CPU updates the states in XLA's fusion of ssm_step
    assert line["metrics"]["ssm_kernel_step_share.falconh1"]["value"] == 0
    assert line["metrics"]["paged_attention_step_share.offline"]["value"] == 0
    # the device's shares of a roofline are not read on the CPU
    assert not set(TRACE) & set(line["metrics"])


@pytest.mark.parametrize("fault", ["other_slot", "state_not_carried"])
def test_a_planted_fault_reads_not_correct(tree, fault):
    dst, _ = tree
    line, out = _run(dst, 0, fault)
    assert line["correct"] is False and line["attempted"] > 0, out[-1500:]


@pytest.fixture(scope="module")
def full():
    sys.path.insert(0, toy_tree.REPO)
    try:
        from tpubench.models import falcon_h1 as fam
    finally:
        sys.path.remove(toy_tree.REPO)
    with open(os.path.join(toy_tree.REPO, "tpubench", "configs",
                           "falcon-h1-34b-serve.json")) as f:
        return fam, json.load(f)


def test_the_cells_shape_counts(full):
    """The configuration file's arithmetic, from the family's functions."""
    fam, config = full
    assert fam.layer_params(config) == 430_120_032
    assert fam.param_count(config) == 4_394_354_048
    assert fam.kv_bytes_per_token(config, 2) == 8 * 1024
    assert fam.state_bytes_per_seq(config, 2) == (16 * 2 ** 20, 120 * 1024)
    s = config["serve"]
    assert s["num_blocks"] == s["max_batch"] * (
        config["n_positions"] // s["block_size"]) + 1
    # a step of 128 sequences at 2.3k tokens: bytes-bound, about 12.8 GB,
    # of which the states read and written are 4.29 GB
    flops, nbytes = fam.decode_least(config, 128 * 2300, 128, 2)
    assert 12.4e9 < nbytes < 13.0e9
    assert flops / 197e12 < nbytes / 819e9
    s_flops, s_bytes = fam.state_update_least(config, 128)
    assert 4 * 2 * 128 * 4 * 2 ** 20 < 4 * s_bytes < 4.4e9


def test_the_file_keeps_the_catalogs_numbers(full):
    """Every key of the published config is in the file as published, but
    the depth; the cut is of depth alone and names no width."""
    _, config = full
    published = {
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504, "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_n_groups": 2, "mamba_n_heads": 32,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20,
        "num_key_value_heads": 4, "num_logits_to_keep": 1,
        "projectors_bias": False, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 100000000000,
        "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False, "vocab_size": 261120}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 4
    assert config["published"] == {"num_hidden_layers": 72}
    assert config["reduced"] == ["num_hidden_layers"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert all(isinstance(v, str) and v for v in config["assumed"].values())
    s = config["serve"]
    assert 0 < s["logit_mean_margin"] < s["logit_margin"]
    assert len(s["logit_margin_why"]) > 200
    assert config["n_positions"] == 2048 + 2048
