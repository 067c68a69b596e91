"""ISSUE 14: quantized collectives with error feedback
(paddle_tpu.distributed.compress).

Gates: blockwise kernel parity (jnp reference vs Pallas interpret),
quantized-allreduce math (+ error feedback) in shard_map, the
8-device e2e train gate (int8:ef wire_bytes <= 0.3x the explicit
fp32 twin, final-loss parity, PADDLE_COMM_COMPRESS unset bit-
identical to the implicit GSPMD program and comm-counter-clean),
bit-identical EF-residual checkpoint resume, the comm_compress chaos
site (raise + bitflip, disarmed provably clean), the PTA08x
sanitizer family (runtime + static, zero-overhead disarmed), the
list-arg collective payload fix, and the README doc-drift gate."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as optim
from paddle_tpu.core import monitor as cmon
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import build_mesh, set_mesh
from paddle_tpu.distributed import collective as C
from paddle_tpu.distributed import compress as comp
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.jit.distributed import DistributedTrainStepCompiler
from paddle_tpu.monitor import chaos
from paddle_tpu.monitor import sanitize as msan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def mesh8():
    prev = mesh_mod.get_mesh()
    mesh = build_mesh({"dp": 8})
    set_mesh(mesh)
    yield mesh
    set_mesh(prev)


def _delta(keys):
    before = {k: cmon.stat_get(k) for k in keys}

    def read():
        return {k: cmon.stat_get(k) - before[k] for k in keys}

    return read


# ---------------------------------------------------------------------------
# config / spec grammar
# ---------------------------------------------------------------------------

def test_spec_grammar():
    cfg = comp.parse_spec("int8:ef:block=256")
    assert (cfg.mode, cfg.ef, cfg.block) == ("int8", True, 256)
    assert comp.parse_spec("fp8").spec() == "fp8"
    assert comp.parse_spec("off") is None and comp.parse_spec("") is None
    assert comp.resolve(None) is None and comp.resolve(False) is None
    assert comp.resolve(cfg) is cfg
    with pytest.raises(ValueError):
        comp.parse_spec("int4")
    with pytest.raises(ValueError):
        comp.parse_spec("int8:bogus=1")
    with pytest.raises(ValueError):
        comp.parse_spec("fp32:ef")  # EF corrects quant error; fp32 has none
    with pytest.raises(ValueError):
        comp.parse_spec("int8:block=100")  # not a 128-multiple


def test_bad_env_spec_is_loud_but_nonfatal(monkeypatch):
    monkeypatch.setenv("PADDLE_COMM_COMPRESS", "int5")
    assert comp.from_env() is None
    assert cmon.stat_get("comm/compress/spec_errors") >= 1


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bound():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 512).astype(np.float32) * 5)
    for mode, rel in (("int8", 1 / 127), ("fp8", 1 / 8)):
        q, s = comp.kernels.quantize_ref(x, 128, mode)
        assert q.dtype == comp.kernels.wire_dtype(mode)
        d = comp.kernels.dequantize_ref(q, s, 128, mode)
        # per-block bound: |x - deq| <= rel * blockwise absmax
        xb = np.asarray(x).reshape(-1, 128)
        db = np.asarray(d).reshape(-1, 128)
        bound = rel * np.abs(xb).max(axis=1, keepdims=True) + 1e-7
        assert (np.abs(xb - db) <= bound).all(), mode


def test_quantize_zero_block_is_exact():
    x = jnp.zeros((256,), jnp.float32)
    for mode in ("int8", "fp8"):
        q, s = comp.kernels.quantize_ref(x, 128, mode)
        d = comp.kernels.dequantize_ref(q, s, 128, mode)
        assert np.asarray(d).max() == 0.0 and np.asarray(s).min() == 1.0


def test_quantize_rejects_non_block_multiple():
    with pytest.raises(ValueError):
        comp.kernels.quantize_ref(jnp.zeros((100,)), 128, "int8")


def test_effective_block_clamps_tiny_payloads():
    """Found driving a 676-param model at the default 1024 block:
    padding to W*block made the 'compressed' wire LARGER than the
    fp32 one. The effective block clamps to one rank's 128-rounded
    segment, bounding padding; large payloads keep cfg.block."""
    cfg = comp.parse_spec("int8")  # default block 1024
    assert comp.effective_block(cfg, 676, 8) == 128
    assert comp.padded_elems(cfg, 676, 8) == 1024
    assert comp.wire_bytes_of(cfg, 1024, block=128) < 676 * 4
    # large payloads: cfg.block wins
    assert comp.effective_block(cfg, 1 << 20, 8) == 1024
    # the compiled tiny-model step really puts fewer bytes on the
    # wire than its fp32 logical payload
    paddle.seed(0)
    mesh = build_mesh({"dp": 8})
    set_mesh(mesh)
    try:
        model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                              nn.Linear(32, 4))
        opt = optim.SGD(learning_rate=0.1,
                        parameters=model.parameters())
        step = DistributedTrainStepCompiler(model, opt, loss_fn=_mse,
                                            mesh=mesh,
                                            comm_compress="int8:ef")
        read = _delta(_COMM_KEYS)
        rng = np.random.RandomState(0)
        step(paddle.to_tensor(rng.randn(16, 16).astype(np.float32)),
             paddle.to_tensor(rng.randn(16, 4).astype(np.float32)))
        d = read()
        assert 0 < d["comm/all_reduce/wire_bytes"] < \
            d["comm/all_reduce/bytes"], d
    finally:
        set_mesh(None)


def test_pallas_int8_kernels_interpret_parity(monkeypatch):
    """The Pallas quant/dequant kernels (PADDLE_PALLAS_FUSION=1,
    interpret mode on CPU) are bit-identical to the jnp reference."""
    monkeypatch.setenv("PADDLE_PALLAS_FUSION", "1")
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, 1024).astype(np.float32) * 3)
    q_ref, s_ref = comp.kernels.quantize_ref(x, 256, "int8")
    q_k, s_k = comp.kernels.quantize_blocks(x, 256, "int8")
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_ref))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_ref))
    d_ref = comp.kernels.dequantize_ref(q_ref, s_ref, 256, "int8")
    d_k = comp.kernels.dequantize_blocks(q_k, s_k, 256, "int8")
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_ref))


# ---------------------------------------------------------------------------
# quantized allreduce in shard_map
# ---------------------------------------------------------------------------

def _flat_allreduce(mesh, data, cfg, iters=1):
    W = data.shape[0]
    sh = NamedSharding(mesh, P("dp"))
    g = jax.device_put(data, sh)
    res = jax.device_put(np.zeros_like(data), sh)

    def island(x, r):
        out, nr = comp.all_reduce_flat(
            x[0], "dp", W, cfg,
            residual=(r[0] if cfg is not None and cfg.ef else None))
        return out, (nr[None] if nr is not None else r)

    f = jax.jit(mesh_mod.shard_map_compat(
        island, mesh, (P("dp"), P("dp")), (P(), P("dp"))))
    outs = []
    for _ in range(iters):
        out, res = f(g, res)
        outs.append(np.asarray(out))
    return outs


def test_quantized_allreduce_matches_sum(mesh8):
    rng = np.random.RandomState(0)
    data = rng.randn(8, 2048).astype(np.float32)
    true = data.sum(0)
    for spec in ("int8:block=128", "fp8:block=128"):
        out, = _flat_allreduce(mesh8, data, comp.parse_spec(spec))
        rel = np.abs(out - true).max() / np.abs(true).max()
        assert rel < 0.05, (spec, rel)
    out, = _flat_allreduce(mesh8, data, comp.parse_spec("fp32"))
    np.testing.assert_allclose(out, true, rtol=1e-5, atol=1e-5)


def test_error_feedback_debiases_repeated_reduce(mesh8):
    """EF's defining property: reducing the SAME payload repeatedly,
    the time-average of the quantized outputs converges to the true
    sum (each step re-feeds the previous step's quantization error),
    while the EF-less path repeats the same biased output forever."""
    rng = np.random.RandomState(3)
    data = rng.randn(8, 2048).astype(np.float32)
    true = data.sum(0)
    plain = _flat_allreduce(mesh8, data,
                            comp.parse_spec("int8:block=128"), 8)
    ef = _flat_allreduce(mesh8, data,
                         comp.parse_spec("int8:ef:block=128"), 8)
    err_plain = np.abs(np.mean(plain, 0) - true).max()
    err_ef = np.abs(np.mean(ef, 0) - true).max()
    assert np.array_equal(plain[0], plain[-1])  # no EF: static bias
    assert err_ef < 0.25 * err_plain, (err_ef, err_plain)


# ---------------------------------------------------------------------------
# e2e train gates (8-device mesh)
# ---------------------------------------------------------------------------

def _mse(o, t):
    return ((o - t) ** 2).mean()


def _build_dp8(compress, **kw):
    paddle.seed(0)
    mesh = build_mesh({"dp": 8})
    set_mesh(mesh)
    model = nn.Sequential(nn.Linear(64, 256), nn.ReLU(),
                          nn.Linear(256, 8))
    opt = optim.AdamW(learning_rate=1e-2,
                      parameters=model.parameters())
    step = DistributedTrainStepCompiler(model, opt, loss_fn=_mse,
                                        mesh=mesh,
                                        comm_compress=compress, **kw)
    return model, step


_COMM_KEYS = ("comm/all_reduce/calls", "comm/all_reduce/bytes",
              "comm/all_reduce/wire_bytes")


def _train(compress, steps=10, target=None, **kw):
    """`target(x)` gives the batch's targets; by default they are
    fresh noise (nothing to learn: parity and counters only)."""
    rng = np.random.RandomState(0)
    xs = [rng.randn(16, 64).astype(np.float32) for _ in range(steps)]
    ys = [rng.randn(16, 8).astype(np.float32) for _ in range(steps)]
    if target is not None:
        ys = [target(x) for x in xs]
    model, step = _build_dp8(compress, **kw)
    read = _delta(_COMM_KEYS)
    losses = [float(step(paddle.to_tensor(x),
                         paddle.to_tensor(y)).item())
              for x, y in zip(xs, ys)]
    comm = read()
    set_mesh(None)
    return losses, comm, step


def test_e2e_int8_ef_wire_ratio_and_loss_parity():
    """THE acceptance gate: int8:ef vs the explicit fp32 twin on the
    8-device mesh — wire_bytes <= 0.3x, loss curve parity, both
    train. The target is a fixed linear map of the inputs: a falling
    loss on fresh batches is then the model learning it, whatever
    the installed jax draws for the initial weights."""
    w = (np.random.RandomState(1).randn(64, 8) / 8).astype(np.float32)
    l_fp32, c_fp32, _ = _train("fp32", target=lambda x: x @ w)
    l_int8, c_int8, _ = _train("int8:ef:block=256",
                               target=lambda x: x @ w)
    # the twins price the same logical payload...
    assert c_int8["comm/all_reduce/bytes"] == \
        c_fp32["comm/all_reduce/bytes"] > 0
    # ...but the quantized wire carries <= 0.3x the bytes
    ratio = (c_int8["comm/all_reduce/wire_bytes"]
             / c_fp32["comm/all_reduce/wire_bytes"])
    assert ratio <= 0.3, ratio
    # loss-curve parity: every step within 2% of the fp32 twin, and
    # both actually train
    for a, b in zip(l_fp32, l_int8):
        assert abs(a - b) <= 2e-2 * max(1.0, abs(a)), (a, b)
    assert l_fp32[-1] < l_fp32[0] and l_int8[-1] < l_int8[0]


def test_e2e_compress_off_is_bit_identical_and_counter_clean():
    """PADDLE_COMM_COMPRESS unset + no argument: the implicit GSPMD
    program — bit-identical losses to the explicit fp32 twin's math
    path is NOT required (different reduction order); what IS
    required: zero explicit comm counters (no island was built) and
    step-for-step identical losses across two identically-seeded
    uncompressed runs."""
    assert not os.environ.get("PADDLE_COMM_COMPRESS")
    l1, c1, step = _train(None)
    assert step._compress is None and step._comm_state == {}
    assert all(v == 0 for v in c1.values()), c1
    l2, c2, _ = _train(None)
    assert l1 == l2


def test_env_config_drives_fit_compilers(monkeypatch):
    """PADDLE_COMM_COMPRESS wires the quantized allreduce into every
    DistributedTrainStepCompiler built WITHOUT an explicit
    comm_compress argument (the Model.fit path)."""
    monkeypatch.setenv("PADDLE_COMM_COMPRESS", "int8:ef:block=256")
    paddle.seed(0)
    mesh = build_mesh({"dp": 8})
    set_mesh(mesh)
    try:
        model = nn.Sequential(nn.Linear(64, 32), nn.ReLU(),
                              nn.Linear(32, 8))
        opt = optim.SGD(learning_rate=0.1,
                        parameters=model.parameters())
        step = DistributedTrainStepCompiler(model, opt, loss_fn=_mse,
                                            mesh=mesh)
        assert step._compress is not None
        read = _delta(_COMM_KEYS)
        rng = np.random.RandomState(0)
        loss = step(paddle.to_tensor(rng.randn(16, 64)
                                     .astype(np.float32)),
                    paddle.to_tensor(rng.randn(16, 8)
                                     .astype(np.float32)))
        assert np.isfinite(float(loss.item()))
        comm = read()
        assert comm["comm/all_reduce/wire_bytes"] > 0
        assert comm["comm/all_reduce/wire_bytes"] < \
            comm["comm/all_reduce/bytes"]
        # the EF residual is real donated state
        assert "residual" in step._comm_state
    finally:
        set_mesh(None)


def test_env_config_disables_on_hybrid_mesh(monkeypatch):
    """An env-driven config on a model-parallel mesh DISABLES (a pod
    job sets the env once; hybrid members keep GSPMD); an explicit
    constructor spec on the same mesh raises."""
    monkeypatch.setenv("PADDLE_COMM_COMPRESS", "int8")
    paddle.seed(0)
    mesh = build_mesh({"dp": 2, "mp": 4})
    set_mesh(mesh)
    try:
        from paddle_tpu.text.models.gpt import (GPTConfig,
                                                GPTForCausalLM)

        cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                        num_heads=2, ffn_hidden=32, max_seq_len=8,
                        remat=False, use_flash_attention=False,
                        dropout=0.0)
        model = GPTForCausalLM(cfg)
        opt = optim.SGD(learning_rate=0.1,
                        parameters=model.parameters())
        step = DistributedTrainStepCompiler(model, opt, mesh=mesh)
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(0, 64, (8, 8))
                               .astype(np.int32))
        loss = step(ids, ids)
        assert np.isfinite(float(loss.item()))
        assert step._compress is None  # disabled, not crashed

        m2 = GPTForCausalLM(cfg)
        o2 = optim.SGD(learning_rate=0.1, parameters=m2.parameters())
        s2 = DistributedTrainStepCompiler(m2, o2, mesh=mesh,
                                          comm_compress="int8")
        with pytest.raises(ValueError, match="comm_compress"):
            s2(ids, ids)
    finally:
        set_mesh(None)


def test_fused_dispatch_and_grad_scaler_compose():
    """steps_per_dispatch=2 + GradScaler + guard_nonfinite over the
    compressed step: the residual rides the scan carry, gradients
    unscale before quantizing, and K fused microsteps match 2K
    sequential single dispatches step-for-step (same quantized
    math)."""
    from paddle_tpu import amp

    rng = np.random.RandomState(0)
    xs = [rng.randn(16, 64).astype(np.float32) for _ in range(8)]
    ys = [rng.randn(16, 8).astype(np.float32) for _ in range(8)]

    _, s1 = _build_dp8("int8:ef:block=256",
                       grad_scaler=None)
    seq = [float(s1(paddle.to_tensor(x), paddle.to_tensor(y)).item())
           for x, y in zip(xs, ys)]
    set_mesh(None)

    _, s2 = _build_dp8("int8:ef:block=256", steps_per_dispatch=2,
                       grad_scaler=None)
    fused = []
    for i in range(0, 8, 2):
        out = s2(paddle.to_tensor(np.stack(xs[i:i + 2])),
                 paddle.to_tensor(np.stack(ys[i:i + 2])))
        fused.extend(float(v) for v in np.asarray(out.numpy()))
    set_mesh(None)
    np.testing.assert_array_equal(seq, fused)

    _, s3 = _build_dp8("int8:ef:block=256", guard_nonfinite=True,
                       grad_scaler=amp.GradScaler(
                           init_loss_scaling=2.0 ** 10))
    scaled = [float(s3(paddle.to_tensor(x),
                       paddle.to_tensor(y)).item())
              for x, y in zip(xs[:4], ys[:4])]
    set_mesh(None)
    assert np.isfinite(scaled).all() and s3.last_skips == 0
    # unscale-before-quantize: the scaled run's losses match the
    # unscaled run's (quantization sees the same gradient values)
    for a, b in zip(seq[:4], scaled):
        assert abs(a - b) <= 1e-5 * max(1.0, abs(a)), (a, b)


# ---------------------------------------------------------------------------
# elastic checkpoint round-trip
# ---------------------------------------------------------------------------

def test_ef_residual_checkpoint_roundtrip_bit_identical():
    """Acceptance: the EF residual round-trips through training-state
    snapshot/restore with bit-identical resumed training — and
    WITHOUT the residual the resumed run diverges (the buffer is
    load-bearing state, not decoration)."""
    rng = np.random.RandomState(0)
    xs = [rng.randn(16, 64).astype(np.float32) for _ in range(10)]
    ys = [rng.randn(16, 8).astype(np.float32) for _ in range(10)]

    m1, s1 = _build_dp8("int8:ef:block=256")
    for i in range(5):
        s1(paddle.to_tensor(xs[i]), paddle.to_tensor(ys[i]))
    # the snapshot a CheckpointManager would host-copy (hapi
    # _training_state reads exactly these fields)
    slots = {k: {s: np.asarray(v) for s, v in sl.items()}
             for k, sl in s1._opt_state.items()}
    residuals = {k: np.asarray(v) for k, v in s1._comm_state.items()}
    assert "residual" in residuals
    assert np.abs(residuals["residual"]).max() > 0  # EF really ran
    sd = {k: np.asarray(v._value if hasattr(v, "_value") else v)
          for k, v in m1.state_dict().items()}
    cont = [float(s1(paddle.to_tensor(xs[i]),
                     paddle.to_tensor(ys[i])).item())
            for i in range(5, 10)]
    set_mesh(None)

    m2, s2 = _build_dp8("int8:ef:block=256")
    m2.set_state_dict(sd)
    s2.restore_state(slots, step=5, comm=residuals)
    resumed = [float(s2(paddle.to_tensor(xs[i]),
                        paddle.to_tensor(ys[i])).item())
               for i in range(5, 10)]
    set_mesh(None)
    assert cont == resumed  # bit-identical

    m3, s3 = _build_dp8("int8:ef:block=256")
    m3.set_state_dict(sd)
    s3.restore_state(slots, step=5)  # residual dropped
    stale = [float(s3(paddle.to_tensor(xs[i]),
                      paddle.to_tensor(ys[i])).item())
             for i in range(5, 10)]
    set_mesh(None)
    assert cont != stale


def test_training_state_snapshot_carries_opt_comm():
    """hapi Model._training_state embeds the residual under
    'opt_comm' and _restore_training_state routes it back into the
    next compiler's preload."""
    from paddle_tpu.hapi import Model
    from paddle_tpu.nn import Linear

    paddle.seed(0)
    mesh = build_mesh({"dp": 8})
    set_mesh(mesh)
    try:
        net = nn.Sequential(Linear(64, 32), nn.ReLU(), Linear(32, 8))
        model = Model(net)
        opt = optim.SGD(learning_rate=0.1, parameters=net.parameters())
        model.prepare(opt, _mse)
        comp_step = DistributedTrainStepCompiler(
            net, opt, loss_fn=_mse, mesh=mesh,
            comm_compress="int8:ef:block=256")
        rng = np.random.RandomState(0)
        comp_step(paddle.to_tensor(rng.randn(16, 64)
                                   .astype(np.float32)),
                  paddle.to_tensor(rng.randn(16, 8)
                                   .astype(np.float32)))
        model._compiled_step = comp_step
        state = model._training_state()
        assert state["opt_comm"] is not None
        assert "residual" in state["opt_comm"]
    finally:
        set_mesh(None)


# ---------------------------------------------------------------------------
# chaos site
# ---------------------------------------------------------------------------

def test_chaos_comm_compress_raise_and_disarmed_clean():
    with chaos.inject("comm_compress", "raise") as rule:
        with pytest.raises(chaos.ChaosInjected):
            _train("int8:block=256", steps=1)
        assert rule.triggers == 1
    set_mesh(None)
    assert cmon.stat_get("chaos/comm_compress/raise/triggered") == 1
    # disarmed rebuild: clean, and no further chaos counters move
    t0 = cmon.stat_get("chaos/comm_compress/raise/triggered")
    losses, _, _ = _train("int8:block=256", steps=2)
    assert np.isfinite(losses).all()
    assert cmon.stat_get("chaos/comm_compress/raise/triggered") == t0


def test_chaos_bitflip_corrupts_one_block_deterministically():
    """The bitflip fault bakes a one-block wire corruption into the
    built program: losses visibly diverge from the clean run but
    stay finite, and the trigger counter proves exactly one
    injection (one build)."""
    clean, _, _ = _train("int8:block=256", steps=4)
    with chaos.inject("comm_compress", "bitflip") as rule:
        hurt, _, _ = _train("int8:block=256", steps=4)
        assert rule.triggers == 1  # once per build, not per step
    set_mesh(None)
    assert np.isfinite(hurt).all()
    assert clean != hurt
    assert cmon.stat_get(
        "chaos/comm_compress/bitflip/triggered") >= 1


def test_chaos_bitflip_rejected_outside_comm_compress():
    with pytest.raises(ValueError):
        chaos.parse_spec("dispatch:bitflip")


# ---------------------------------------------------------------------------
# PTA08x sanitizers
# ---------------------------------------------------------------------------

def test_pta080_undonated_residual_raises_under_sanitize():
    msan.configure("compress")
    try:
        with pytest.raises(ValueError, match="PTA080"):
            _train("int8:ef:block=256", steps=1, donate=False)
    finally:
        msan.disarm()
        set_mesh(None)
    assert cmon.stat_get("analysis/PTA080/findings") >= 1
    # disarmed: the same build proceeds (wasteful but workable)
    losses, _, _ = _train("int8:ef:block=256", steps=1, donate=False)
    assert np.isfinite(losses).all()


def test_pta081_nonsum_compress_falls_back(mesh8):
    g = mesh_mod.new_group_for_axes(("dp",))
    data = np.random.RandomState(0).randn(8, 256).astype(np.float32)

    def island(x):
        t = Tensor(x[0], stop_gradient=True, _internal=True)
        C.all_reduce(t, op=C.ReduceOp.MAX, group=g, compress="int8")
        return t._value

    f = jax.jit(mesh_mod.shard_map_compat(island, mesh8,
                                          (P("dp"),), P()))
    out = f(jax.device_put(data, NamedSharding(mesh8, P("dp"))))
    np.testing.assert_allclose(np.asarray(out), data.max(0),
                               rtol=1e-6)  # silent fp32 fallback
    msan.configure("compress")
    try:
        f2 = jax.jit(mesh_mod.shard_map_compat(island, mesh8,
                                               (P("dp"),), P()))
        with pytest.raises(ValueError, match="PTA081"):
            f2(jax.device_put(data + 1,
                              NamedSharding(mesh8, P("dp"))))
    finally:
        msan.disarm()
    assert cmon.stat_get("analysis/PTA081/findings") >= 1


def test_pta081_integer_dtype_falls_back(mesh8):
    g = mesh_mod.new_group_for_axes(("dp",))
    data = np.arange(8 * 256, dtype=np.int32).reshape(8, 256)

    def island(x):
        t = Tensor(x[0], stop_gradient=True, _internal=True)
        C.all_reduce(t, group=g, compress="int8")
        return t._value

    f = jax.jit(mesh_mod.shard_map_compat(island, mesh8,
                                          (P("dp"),), P()))
    out = f(jax.device_put(data, NamedSharding(mesh8, P("dp"))))
    np.testing.assert_array_equal(np.asarray(out), data.sum(0))


def test_compress_static_lints():
    from paddle_tpu.analysis.compress import lint_compress_source

    src = """
def bad(grads, res, C, ReduceOp):
    reduce_tree(grads, SEGS, 'dp', 8, CFG, residual=res)
    out = all_reduce_flat(flat, 'dp', 8, CFG, residual=res)
    C.all_reduce(t, op=ReduceOp.MAX, compress="int8")

def also_bad(grads, res):
    g, new_res = reduce_tree(grads, SEGS, 'dp', 8, CFG, residual=res)
    return g

def self_update_dropped(grads, res):
    out, res = reduce_tree(grads, SEGS, 'dp', 8, CFG, residual=res)
    return out

def fine(grads, res, C):
    g, new_res = reduce_tree(grads, SEGS, 'dp', 8, CFG, residual=res)
    C.all_reduce(t, op=ReduceOp.SUM, compress="int8")
    return g, new_res

def fine_ef_loop(grads, res, data):
    for _ in data:
        grads, res = reduce_tree(grads, SEGS, 'dp', 8, CFG,
                                 residual=res)
    return grads
"""
    rep = lint_compress_source(src, filename="x.py")
    codes = sorted(f.code for f in rep.findings)
    assert codes.count("PTA081") == 1
    # discarded call + bound-but-dead result + dead tuple slot +
    # the straight-line self-update whose RHS read is the OLD
    # binding (the canonical EF LOOP, where that read consumes the
    # previous iteration's new residual, stays clean)
    assert codes.count("PTA080") == 4, [f.format() for f in
                                        rep.findings]
    # the clean function contributes nothing
    fine_line = src[:src.index("def fine")].count("\n") + 1
    assert all(f.line < fine_line for f in rep.findings)


def test_sanitize_family_registered():
    assert "compress" in msan.FAMILIES
    fams = msan.parse_spec("compress")
    assert "compress" in fams
    from paddle_tpu.analysis.cli import SANITIZE_FAMILIES

    assert "compress" in SANITIZE_FAMILIES


def test_disarmed_run_leaves_zero_sanitize_counters():
    """The bench provenance contract: a compressed run with nothing
    armed must not move sanitize/PTA08x counters."""
    before = (cmon.stat_get("analysis/PTA080/findings"),
              cmon.stat_get("analysis/PTA081/findings"),
              cmon.stat_get("sanitize/findings"))
    losses, _, _ = _train("int8:ef:block=256", steps=2)
    assert np.isfinite(losses).all()
    after = (cmon.stat_get("analysis/PTA080/findings"),
             cmon.stat_get("analysis/PTA081/findings"),
             cmon.stat_get("sanitize/findings"))
    assert before == after


# ---------------------------------------------------------------------------
# collective payload accounting (the ISSUE-14 fix)
# ---------------------------------------------------------------------------

def test_all_gather_counts_full_payload(mesh8):
    """Regression (ISSUE-14 satellite): comm/all_gather/bytes (and
    the flight event) price the FULL gathered payload — group_size x
    the per-rank tensor — not the first tensor's bytes."""
    g = mesh_mod.new_group_for_axes(("dp",))
    data = np.random.RandomState(0).randn(8, 512).astype(np.float32)
    read = _delta(("comm/all_gather/bytes",
                   "comm/all_gather/wire_bytes"))

    def island(x):
        parts = []
        C.all_gather(parts, Tensor(x[0], stop_gradient=True,
                                   _internal=True), group=g)
        return jnp.stack([p._value for p in parts], axis=0)

    f = jax.jit(mesh_mod.shard_map_compat(island, mesh8,
                                          (P("dp"),), P()))
    out = f(jax.device_put(data, NamedSharding(mesh8, P("dp"))))
    np.testing.assert_allclose(np.asarray(out), data, rtol=1e-6)
    d = read()
    assert d["comm/all_gather/bytes"] == 8 * 512 * 4
    assert d["comm/all_gather/wire_bytes"] == 8 * 512 * 4


def test_plain_collectives_wire_equals_bytes(mesh8):
    g = mesh_mod.new_group_for_axes(("dp",))
    data = np.random.RandomState(0).randn(8, 128).astype(np.float32)
    read = _delta(("comm/all_reduce/bytes",
                   "comm/all_reduce/wire_bytes"))

    def island(x):
        t = Tensor(x[0], stop_gradient=True, _internal=True)
        C.all_reduce(t, group=g)
        return t._value

    f = jax.jit(mesh_mod.shard_map_compat(island, mesh8,
                                          (P("dp"),), P()))
    f(jax.device_put(data, NamedSharding(mesh8, P("dp"))))
    d = read()
    assert d["comm/all_reduce/bytes"] == 128 * 4
    assert d["comm/all_reduce/wire_bytes"] == 128 * 4


def test_scatter_counts_list_payload():
    t = paddle.to_tensor(np.zeros((4, 4), np.float32))
    parts = [paddle.to_tensor(np.full((4, 4), i, np.float32))
             for i in range(2)]
    read = _delta(("comm/scatter/bytes",))
    C.scatter(t, parts, src=0)
    assert read()["comm/scatter/bytes"] == 2 * 4 * 4 * 4


# ---------------------------------------------------------------------------
# doc drift
# ---------------------------------------------------------------------------

class TestDocDrift:
    def _readme(self):
        with open(os.path.join(REPO, "README.md")) as f:
            return f.read()

    def test_readme_covers_quantized_comm(self):
        doc = self._readme()
        assert "Quantized communication" in doc
        for needle in ("PADDLE_COMM_COMPRESS", "PADDLE_COMM_BLOCK",
                       "int8", "error feedback", "wire_bytes",
                       "comm_compress"):
            assert needle in doc, f"{needle!r} missing from README"

    def test_readme_covers_pta08x_and_chaos_site(self):
        doc = self._readme()
        for code in ("PTA080", "PTA081"):
            assert code in doc, f"{code} missing from README"
        assert "comm_compress" in doc and "bitflip" in doc
