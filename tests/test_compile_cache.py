"""Persistent on-disk XLA compile cache (ISSUE 8): round-trip, torn/
corrupt-entry tolerance, LRU cap, and the CostModel/planner leg.

The CPU PJRT runtime serializes executables, so the full
serialize → atomic publish → deserialize_and_load path runs for real
here — no mocks."""
import os
import pickle

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.monitor import stat_get, stat_reset
from paddle_tpu.jit import persistent_cache as pcache
from paddle_tpu.jit import to_static
from paddle_tpu.monitor import chaos


def _counters():
    return {k: stat_get(f"jit/persistent_cache/{k}")
            for k in ("hits", "misses", "errors", "bytes")}


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    d = tmp_path / "ccache"
    monkeypatch.setenv("PADDLE_COMPILE_CACHE_DIR", str(d))
    monkeypatch.delenv("PADDLE_COMPILE_CACHE_MAX_BYTES", raising=False)
    stat_reset()
    return d


def _entries(d):
    return sorted(p for p in os.listdir(d) if p.endswith(".pdx")) \
        if os.path.isdir(d) else []


def _fn(x):
    return x * 3.0 + 1.0


def test_to_static_cold_miss_then_warm_hit(cache_dir):
    """A fresh StaticFunction over the same program loads the disk
    entry instead of recompiling — the in-memory program cache never
    sees the second wrapper."""
    x = paddle.to_tensor(np.full((4, 4), 2.0, np.float32))
    y1 = to_static(_fn)(x)
    c = _counters()
    assert c["misses"] == 1 and c["hits"] == 0 and c["errors"] == 0
    assert len(_entries(cache_dir)) == 1
    assert c["bytes"] > 0

    y2 = to_static(_fn)(x)  # fresh wrapper, same lowered module
    c = _counters()
    assert c["hits"] == 1 and c["misses"] == 1 and c["errors"] == 0
    np.testing.assert_array_equal(np.asarray(y1._value),
                                  np.asarray(y2._value))


def test_off_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv("PADDLE_COMPILE_CACHE_DIR", raising=False)
    assert not pcache.enabled()
    stat_reset()
    x = paddle.to_tensor(np.ones((3, 3), np.float32))
    to_static(_fn)(x)
    c = _counters()
    assert c["misses"] == 0 and c["hits"] == 0


def test_corrupt_entry_falls_back_to_compile(cache_dir):
    x = paddle.to_tensor(np.ones((5, 5), np.float32))
    y1 = to_static(_fn)(x)
    (name,) = _entries(cache_dir)
    path = os.path.join(cache_dir, name)
    with open(path, "wb") as f:
        f.write(b"\x00garbage not a pickle")
    y2 = to_static(_fn)(x)
    c = _counters()
    assert c["errors"] >= 1
    assert c["misses"] == 2  # corrupt read cost a miss, not a crash
    np.testing.assert_array_equal(np.asarray(y1._value),
                                  np.asarray(y2._value))
    # the bad entry was evicted and replaced by a fresh good one
    (name2,) = _entries(cache_dir)
    with open(os.path.join(cache_dir, name2), "rb") as f:
        assert pickle.load(f)["schema"].startswith("paddle_tpu")


def test_truncated_payload_tolerated(cache_dir):
    """A structurally valid pickle whose executable payload is torn
    mid-byte must fail at deserialize_and_load and fall back."""
    x = paddle.to_tensor(np.ones((6, 6), np.float32))
    to_static(_fn)(x)
    (name,) = _entries(cache_dir)
    path = os.path.join(cache_dir, name)
    with open(path, "rb") as f:
        ent = pickle.load(f)
    ent["payload"] = ent["payload"][:len(ent["payload"]) // 3]
    with open(path, "wb") as f:
        pickle.dump(ent, f)
    y = to_static(_fn)(x)
    assert _counters()["errors"] >= 1
    np.testing.assert_allclose(np.asarray(y._value),
                               np.full((6, 6), 4.0, np.float32))


def test_chaos_torn_cache_write(cache_dir):
    """The ckpt_write-style torn-write injection, reused for cache
    files: the write leaves a partial artifact and counts an error;
    the next run classifies it corrupt and recompiles cleanly."""
    x = paddle.to_tensor(np.ones((7, 7), np.float32))
    with chaos.inject("cache_write", "torn"):
        y1 = to_static(_fn)(x)
    c = _counters()
    assert c["errors"] >= 1 and c["misses"] == 1
    assert len(_entries(cache_dir)) == 1  # the torn partial artifact
    assert stat_get("chaos/cache_write/torn/triggered") == 1

    # disarmed: torn entry detected, evicted, fresh entry published
    y2 = to_static(_fn)(x)
    c = _counters()
    assert c["misses"] == 2 and c["hits"] == 0
    np.testing.assert_array_equal(np.asarray(y1._value),
                                  np.asarray(y2._value))
    y3 = to_static(_fn)(x)
    assert _counters()["hits"] == 1
    assert float(y3._value[0, 0]) == 4.0


def test_chaos_enospc_cache_write(cache_dir):
    """A full filesystem on publish costs an error, never a failure."""
    x = paddle.to_tensor(np.ones((9, 9), np.float32))
    with chaos.inject("cache_write", "enospc"):
        y = to_static(_fn)(x)
    c = _counters()
    assert c["errors"] >= 1 and c["misses"] == 1
    assert _entries(cache_dir) == []
    np.testing.assert_allclose(np.asarray(y._value), 4.0)


def test_lru_eviction_respects_max_bytes(cache_dir, monkeypatch):
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    to_static(_fn)(x)
    (name,) = _entries(cache_dir)
    size = os.path.getsize(os.path.join(cache_dir, name))
    # cap below one entry: the next publish evicts the older entry
    monkeypatch.setenv("PADDLE_COMPILE_CACHE_MAX_BYTES", str(size - 1))

    def g(x):
        return x - 5.0

    to_static(g)(x)
    ents = _entries(cache_dir)
    assert len(ents) <= 1
    assert stat_get("jit/persistent_cache/bytes") <= size


def _linear_step_losses():
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStepCompiler

    paddle.seed(0)
    net = nn.Linear(16, 4)
    ce = nn.CrossEntropyLoss()
    opt = optim.Adam(learning_rate=1e-3, parameters=net.parameters())
    step = TrainStepCompiler(net, opt, lambda o, t: ce(o, t))
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(4, 16).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 4, (4,)).astype(np.int64))
    step(x, y)
    return float(step(x, y).item())


def test_train_step_compiler_warm_hit_cross_process(cache_dir):
    """The donated fwd+bwd+update program round-trips through the
    cache across PROCESSES — the fleet-rollout/bench-rerun contract.
    A subprocess publishes the cold entry; THIS process then builds
    the same program, hits it, and trains to the same loss."""
    import subprocess
    import sys

    script = ("import os, sys\n"
              "sys.path.insert(0, os.getcwd())\n"
              "from tests.test_compile_cache import _linear_step_losses\n"
              "from paddle_tpu.core.monitor import stat_get\n"
              "loss = _linear_step_losses()\n"
              "print('COLD', stat_get('jit/persistent_cache/misses'),"
              " stat_get('jit/persistent_cache/hits'),"
              " stat_get('jit/persistent_cache/errors'), loss)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_COMPILE_CACHE_DIR=str(cache_dir))
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert p.returncode == 0, p.stderr[-2000:]
    cold = [ln for ln in p.stdout.splitlines()
            if ln.startswith("COLD")][0].split()
    assert int(cold[1]) >= 1 and int(cold[2]) == 0  # cold: miss
    assert int(cold[3]) == 0
    assert len(_entries(cache_dir)) >= 1
    warm_loss = _linear_step_losses()     # warm leg, in-process
    c = _counters()
    assert c["hits"] >= 1 and c["errors"] == 0
    assert float(cold[4]) == warm_loss    # bit-identical training


def test_cost_model_probe_reuses_cache(cache_dir):
    """Planner probes (static_cost / profile_measure) consult the
    persistent cache: a fresh CostModel instance hits the entry a
    previous sweep published."""
    import jax.numpy as jnp

    from paddle_tpu.cost_model import CostModel

    def candidate(a, b):
        return (a @ b).sum()

    args = (jnp.ones((32, 16)), jnp.ones((16, 8)))
    cm1 = CostModel()
    cost = cm1.static_cost(candidate, *args)
    assert _counters()["misses"] == 1
    assert cost.get("flops", 0) > 0
    cm2 = CostModel()  # a later sweep, fresh in-memory caches
    dt = cm2.profile_measure(candidate, *args, warmup=1, iters=2)
    assert dt > 0
    c = _counters()
    assert c["hits"] == 1 and c["misses"] == 1


def test_persisted_program_survives_differentiable_call(cache_dir):
    """A warm to_static function used on the DIFFERENTIABLE path
    (apply_op's vjp traces through it with tracers) must detour to
    the jitted fn for that call WITHOUT latching the permanent
    fallback — later concrete calls keep the cached executable
    (review regression: the latch silently turned warm starts back
    into cold compiles)."""
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import _PersistedProgram

    def run(train):
        net = nn.Linear(6, 6)
        sf = to_static(net.forward)
        x = paddle.to_tensor(np.ones((2, 6), np.float32))
        if train:
            y = sf(x)
            (y * y).mean().backward()
        else:
            with paddle.no_grad():
                sf(x)
        (entry,) = sf._compiled.values()
        return entry[0]

    run(train=False)  # cold: publish the entry
    prog = run(train=True)  # warm + differentiable
    assert isinstance(prog, _PersistedProgram)
    assert not prog._fallback
    c = _counters()
    assert c["hits"] >= 1 and c["errors"] == 0


def test_warm_hit_dispatches_on_the_devices_it_was_compiled_for(cache_dir):
    """The 8-virtual-device regression: a deserialized executable must
    load onto the devices its program was compiled for — one device,
    or a 4-device mesh in a non-default order — not onto every device
    of the backend (`Expected args to execute_sharded_on_local_devices
    to have 8 shards, got: [1]` at the first dispatch)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert jax.device_count() == 8
    devs = [jax.devices()[i] for i in (5, 2, 7, 0)]
    mesh = Mesh(np.array(devs).reshape(2, 2), ("a", "b"))
    sharded = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                             NamedSharding(mesh, P("a", "b")))
    single = jnp.arange(16.0).reshape(4, 4)
    fn = jax.jit(lambda x: x @ x.T + x)
    for label, x in (("mesh", sharded), ("single", single)):
        cold, out_c = pcache.load_or_compile(fn.lower(x), label)
        warm, out_w = pcache.load_or_compile(fn.lower(x), label)
        assert (out_c, out_w) == ("miss", "hit")
        got = warm(x)                      # the dispatch that crashed
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(cold(x)))
        assert got.sharding.device_set == x.sharding.device_set
    assert _counters()["errors"] == 0
