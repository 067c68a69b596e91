"""Hard failures and the compile-cache rule of the chip bring-up (ISSUE
21): places that used to hide the device now raise, and JAX's persistent
compilation cache is placed by one resolver. The chip itself is exercised
by `python chip_smoke.py`, not here."""
import os
import subprocess
import sys
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_tpu(kind="TPU v5 lite"):
    return types.SimpleNamespace(platform="tpu", device_kind=kind, id=0)


# -- places ------------------------------------------------------------------

def test_explicit_tpu_place_without_a_tpu_raises(monkeypatch):
    from paddle_tpu.core import place

    with pytest.raises(RuntimeError, match="no tpu device"):
        place.device_of(place.TPUPlace(0))
    monkeypatch.setattr(place, "_current_place", None)
    place.set_device("tpu")
    with pytest.raises(RuntimeError, match="no tpu device"):
        place.current_device()


def test_place_index_past_the_local_devices_raises():
    from paddle_tpu.core import place

    n = len(jax.local_devices())
    assert place.device_of(place.CPUPlace(n - 1)).platform == "cpu"
    with pytest.raises(RuntimeError, match="only"):
        place.device_of(place.CPUPlace(n))


def test_default_place_without_a_tpu_is_cpu():
    from paddle_tpu.core import place

    assert place._default_place() == place.CPUPlace(0)


# -- peak table --------------------------------------------------------------

def test_device_peaks_unknown_accelerator_kind_raises(monkeypatch):
    from paddle_tpu.monitor import perf

    jax.devices()  # the backend is live: device_peaks reads the device
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_fake_tpu("TPU v9 mega")])
    with pytest.raises(ValueError, match="PEAK_TABLE"):
        perf.device_peaks()
    monkeypatch.setattr(jax, "devices", lambda *a: [_fake_tpu()])
    pk = perf.device_peaks()
    assert pk["matched"] == "v5e" and pk["peak_tflops"] == 197.0


# -- KV pool sizing ----------------------------------------------------------

def test_kv_pool_needs_pjrt_stats_on_an_accelerator(monkeypatch):
    from paddle_tpu.inference.serving import kv_cache
    from paddle_tpu.monitor import memory

    monkeypatch.delenv("PADDLE_SERVE_POOL_BYTES", raising=False)
    per_block = 1 << 20
    assert kv_cache.auto_num_blocks(per_block) == 64 + 1  # CPU: 64 MiB
    monkeypatch.setattr(jax, "devices", lambda *a: [_fake_tpu()])
    monkeypatch.setattr(
        memory, "memory_stats",
        lambda dev=None: {"source": "census", "allocated_bytes": 0})
    with pytest.raises(RuntimeError, match="bytes_limit"):
        kv_cache.auto_num_blocks(per_block)
    monkeypatch.setattr(
        memory, "memory_stats",
        lambda dev=None: {"source": "pjrt", "bytes_limit": 16 << 30,
                          "bytes_in_use": 2 << 30})
    blocks = kv_cache.auto_num_blocks(per_block, fraction=0.5)
    assert blocks == (7 << 30) // per_block + 1
    # an explicit budget never consults the device
    assert kv_cache.auto_num_blocks(per_block, pool_bytes=8 << 20) == 9


# -- compile cache -----------------------------------------------------------

def _resolver_from(cwd, env):
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]);"
         "from paddle_tpu.jit import persistent_cache as p;"
         "print(p.native_cache_dir())", REPO],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_native_cache_dir_is_the_env_or_one_fixed_checkout_path(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    # unset: two processes started in different directories agree, and
    # the path is inside the checkout that holds the package
    a = _resolver_from(str(tmp_path), env)
    b = _resolver_from(REPO, env)
    assert a == b == os.path.join(REPO, ".jax_cache")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "outside")
    assert _resolver_from(str(tmp_path), env) == str(tmp_path / "outside")


def test_armed_native_cache_writes_where_the_environment_says(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, arm_native() sets no directory
    of its own: jax's config still names the environment's, compiled
    programs land there, and a second process hits them."""
    d = tmp_path / "jaxcache"
    script = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "import jax, jax.numpy as jnp\n"
        "from paddle_tpu.jit import persistent_cache as p\n"
        "assert p.arm_native() == sys.argv[2]\n"
        "assert p.arm_native() == sys.argv[2]  # idempotent\n"
        "assert jax.config.jax_compilation_cache_dir == sys.argv[2]\n"
        "jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones((8, 8)))"
        ".block_until_ready()\n"
        "s = p.native_cache_stats()\n"
        "print('STATS', s['requests'], s['hits'], s['misses'])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(d))

    def run():
        out = subprocess.run([sys.executable, "-c", script, REPO, str(d)],
                             env=env, capture_output=True, text=True,
                             timeout=300, cwd=str(tmp_path))
        assert out.returncode == 0, out.stderr[-2000:]
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("STATS")][-1]
        return [int(v) for v in line.split()[1:]]

    req, hits, misses = run()
    assert req >= 1 and hits == 0 and misses == req
    assert len(os.listdir(d)) >= 1
    req2, hits2, misses2 = run()
    assert req2 == req and hits2 == req2 and misses2 == 0


_WARM_START = {
    # the donated fwd+bwd+update program: the loss after two steps
    "train_step": (
        "import numpy as np\n"
        "import paddle_tpu as paddle\n"
        "import paddle_tpu.nn as nn, paddle_tpu.optimizer as optim\n"
        "from paddle_tpu.jit import TrainStepCompiler\n"
        "paddle.seed(0)\n"
        "net = nn.Linear(16, 4)\n"
        "ce = nn.CrossEntropyLoss()\n"
        "opt = optim.Adam(learning_rate=1e-3,"
        " parameters=net.parameters())\n"
        "step = TrainStepCompiler(net, opt, lambda o, t: ce(o, t))\n"
        "rng = np.random.RandomState(0)\n"
        "x = paddle.to_tensor(rng.randn(4, 16).astype(np.float32))\n"
        "y = paddle.to_tensor(rng.randint(0, 4, (4,)).astype(np.int64))\n"
        "step(x, y)\n"
        "print('RESULT', repr(float(step(x, y).item())))\n"),
    # the engine's prefill and decode programs: the tokens they emit
    "engine_decode": (
        "import paddle_tpu as paddle\n"
        "from paddle_tpu.inference.serving import LLMEngine,"
        " SamplingParams\n"
        "from paddle_tpu.text.models.gpt import GPTConfig,"
        " GPTForCausalLM\n"
        "paddle.seed(0)\n"
        "model = GPTForCausalLM(GPTConfig(vocab_size=128,"
        " hidden_size=32, num_layers=2, num_heads=2, ffn_hidden=64,"
        " max_seq_len=32, dropout=0.0, use_flash_attention=False))\n"
        "model.eval()\n"
        "engine = LLMEngine(model, max_batch=2, block_size=4,"
        " num_blocks=16)\n"
        "print('RESULT', engine.generate([[1, 2, 3], [4, 5]],"
        " SamplingParams(max_new_tokens=6)))\n"),
}


@pytest.mark.parametrize("what", sorted(_WARM_START))
def test_a_second_process_starts_warm_from_the_native_cache(tmp_path,
                                                            what):
    """The warm start a user keeps: with the native cache armed, the
    programs a first process compiled (a TrainStepCompiler step, an
    LLMEngine's prefill and decode) are loaded by a second one, which
    computes the same loss / tokens."""
    script = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from paddle_tpu.jit import persistent_cache as p\n"
        "p.arm_native()\n" + _WARM_START[what] +
        "s = p.native_cache_stats()\n"
        "print('STATS', s['requests'], s['hits'], s['misses'])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jaxcache"))

    def run():
        out = subprocess.run([sys.executable, "-c", script, REPO],
                             env=env, capture_output=True, text=True,
                             timeout=300, cwd=str(tmp_path))
        assert out.returncode == 0, out.stderr[-2000:]
        lines = {ln.split()[0]: ln.split(None, 1)[1]
                 for ln in out.stdout.splitlines()
                 if ln.startswith(("RESULT", "STATS"))}
        return lines["RESULT"], [int(v) for v in lines["STATS"].split()]

    cold, (req, hits, misses) = run()
    assert req >= 2 and hits == 0 and misses == req
    warm, (req2, hits2, misses2) = run()
    assert warm == cold
    assert req2 == req and hits2 == req2 and misses2 == 0


# -- bench exit code ---------------------------------------------------------

def test_bench_main_fails_when_a_config_raised():
    """In a process of its own: bench.main() asserts on the process-wide
    stat registry, which earlier tests of a suite run have written to."""
    script = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "import bench\n"
        "def ok(on_tpu):\n"
        "    return {'value': 1.0, 'unit': 'x', 'window_spread': [1.0]}\n"
        "def boom(on_tpu):\n"
        "    raise RuntimeError('kernel refused to compile')\n"
        "for n in [n for n in vars(bench) if n.startswith('bench_')]:\n"
        "    setattr(bench, n, ok)\n"
        "print('RC_OK', bench.main([]))\n"
        "bench.bench_gpt2 = boom\n"
        "print('RC_BOOM', bench.main([]))\n")
    out = subprocess.run([sys.executable, "-c", script, REPO],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "RC_OK 0" in out.stdout and "RC_BOOM 1" in out.stdout
    assert "gpt2_345m FAILED: kernel refused to compile" in out.stderr
    assert '"error": "RuntimeError: kernel refused to compile"' \
        in out.stdout


# -- a program's key does not hold the checkout's path (ISSUE 33) -------------

_LOWER_KERNEL_PROGRAMS = """
import functools, hashlib, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax, jax.numpy as jnp
from paddle_tpu.jit import persistent_cache
persistent_cache.arm_native()
if sys.argv[3] == "cleared":
    jax.config.update("jax_hlo_source_file_canonicalization_regex", None)
import paddle_tpu, test_serving_tpu_compile as shapes
assert paddle_tpu.__file__.startswith(sys.argv[1]), paddle_tpu.__file__

def sd(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)

_, programs, kw = shapes._mla_programs(sd)
mla = programs["decode"] + ((3,), kw)
fn, donated, args, _, _ = shapes._cell_program(sd, "decode", jnp.float32)
for name, (fn, args, donated, kw) in (
        ("mla_decode", mla),
        ("gpt2_paged_decode", (fn, args, donated, shapes.KW))):
    text = jax.jit(functools.partial(fn, use_kernel=True, **kw),
                   donate_argnums=donated).trace(*args).lower(
                       lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    print("DIGEST", name, hashlib.sha256(text.encode()).hexdigest())
"""


@pytest.fixture(scope="module")
def kernel_program_digests(tmp_path_factory):
    """{(copy, regex): {program: digest of its text}}: the MLA decode
    and GPT-2's paged decode at their cells' widths, lowered for the
    TPU (cross-platform: no libtpu, no topology) in a process of
    its own from each of two copies of the package at different
    paths, armed by `arm_native()`, with its regex kept and
    cleared."""
    import shutil

    tmp = tmp_path_factory.mktemp("checkouts")
    tests = os.path.join(REPO, "tests")
    roots = [tmp / "a", tmp / "another" / "place"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jaxcache"))
    out = {}
    for copy, root in enumerate(roots):
        shutil.copytree(os.path.join(REPO, "paddle_tpu"),
                        root / "paddle_tpu",
                        ignore=shutil.ignore_patterns("__pycache__"))
        for regex in ("kept", "cleared"):
            run = subprocess.run(
                [sys.executable, "-c", _LOWER_KERNEL_PROGRAMS, str(root),
                 tests, regex], env=env, capture_output=True, text=True,
                timeout=300, cwd=str(root))
            assert run.returncode == 0, run.stderr[-2000:]
            out[copy, regex] = dict(
                ln.split()[1:] for ln in run.stdout.splitlines()
                if ln.startswith("DIGEST"))
    return out


@pytest.mark.parametrize("program", ["mla_decode", "gpt2_paged_decode"])
def test_a_kernel_programs_text_does_not_hold_the_checkouts_path(
        kernel_program_digests, program):
    """A program that holds a Pallas kernel carries the kernel's
    source locations in its lowered text (the Mosaic module inside
    the custom call), and the text is the persistent cache's key.
    From two copies of the package it is ONE text: a checkout
    unpacked elsewhere finds what it compiled. With the regex
    cleared it is two, so this test sees what it guards."""
    d = kernel_program_digests
    assert d[0, "kept"][program] == d[1, "kept"][program]
    assert d[0, "cleared"][program] != d[1, "cleared"][program]
