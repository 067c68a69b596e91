"""The functions that compute operations and bytes, against hand-worked
GPT-2 345M values."""
import json
import os

import pytest

from tpubench.models import gpt2

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module", params=["gpt2-345m-train", "gpt2-345m-serve"])
def config(request):
    with open(os.path.join(REPO, "tpubench", "configs",
                           request.param + ".json")) as f:
        return json.load(f)


def test_parameters(config):
    # wte 50304*1024 + wpe 1024*1024 + 24 * 12 596 224 + final norm 2048
    layer = 3148800 + 1049600 + 4198400 + 4195328 + 4096
    assert layer == 12596224
    assert gpt2.param_count(config) == 51511296 + 1048576 + 24 * layer + 2048
    assert gpt2.param_count(config) == 354871296


def test_train_flops_per_token(config):
    six_n = 6 * 354871296
    assert six_n / 1e9 == pytest.approx(2.129, abs=5e-4)
    total = gpt2.train_flops_per_token(config, 1024)
    # causal attention at 1024: 24 layers * 3 passes * 2 matmuls * 2 flops *
    # 512 keys on average * 1024 hidden = 150 994 944
    assert total - six_n == 24 * 3 * 2 * 2 * 512 * 1024
    assert (total - six_n) / 1e9 == pytest.approx(0.151, abs=5e-4)
    # 43 150 tokens/s is 49.9 % of 197 TFLOP/s (ledger, PR 22: 49.77 % at
    # 43 176 by its own formula)
    assert total * 43150 / 197e12 == pytest.approx(0.4994, abs=1e-3)


def test_flash_kernel_needs(config):
    # the kernels' share of the step is the attention term exactly
    per_token = gpt2.flash_flops_per_step(config, 16, 1024) / (16 * 1024)
    assert per_token == 24 * 3 * 2 * 2 * 512 * 1024
    # q, k, v, o forward; q, k, v, o, do, dq, dk, dv backward; bf16
    assert gpt2.flash_bytes_per_step(config, 16, 1024) == \
        24 * 12 * 16 * 1024 * 1024 * 2
    # at head size 64 nearly balanced on a v5e: 12.6 ms of flops, 11.8 ms
    # of bytes a step
    assert gpt2.flash_flops_per_step(config, 16, 1024) / 197e12 == \
        pytest.approx(12.56e-3, rel=1e-3)
    assert gpt2.flash_bytes_per_step(config, 16, 1024) / 819e9 == \
        pytest.approx(11.80e-3, rel=1e-3)


def test_decode_needs(config):
    assert gpt2.weight_bytes(config, 4) == 1419485184
    assert gpt2.kv_bytes_per_token(config, 4) == 2 * 24 * 1024 * 4
    flops, nbytes = gpt2.decode_least(config, 4000, 32, 4)
    assert nbytes == 1419485184 + 196608 * 4000
    assert flops == 2 * 354871296 * 32 + 4 * 24 * 1024 * 4000
    # bytes-bound: 2.7 ms against 0.12 ms
    assert nbytes / 819e9 > 10 * flops / 197e12
