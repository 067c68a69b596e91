"""ISSUE 17 — PTA09x precision sanitizer: static low-precision hazard
analysis + the runtime numerics probe.

Each static detector (PTA090/091/092/094/095) is proven against a
seeded hazard AND its clean twin; two historical-bug redos gate the
anchors (the bf16-accumulation and fp16-eps-underflow classes must
name the offending eqn/literal, not just the program). The runtime
half: PTA093 aborts a master-weightless fp16 build under
`PADDLE_SANITIZE=numerics`, the fused stats probe attributes an
injected fp16 overflow to the offending tensor (findings + flight
dump bundle), GradScaler backoff/growth annotate the flight timeline,
and DISARMED the lowering is bit-identical with zero numerics
counters — the same zero-overhead contract every family carries.
Plus: spec grammar (`numerics:sample=N:absmax=T`), CLI `--sanitize
numerics` AST leg, the amp list audit, and the PTA-code doc-drift
gate against the README table.
"""
import json
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis, nn, optimizer as optim
from paddle_tpu.analysis import precision
from paddle_tpu.core.monitor import registry
from paddle_tpu.jit import InputSpec
from paddle_tpu.monitor import numerics as num
from paddle_tpu.monitor import sanitize as san

THIS_FILE = __file__


@pytest.fixture(autouse=True)
def _clean_numerics():
    yield
    san.disarm()
    san.clear_findings()
    num.clear()


def _codes(report):
    return {f.code for f in report.findings}


def _only(report, code):
    hits = [f for f in report.findings if f.code == code]
    assert hits, f"expected {code}, got {report.findings}"
    return hits[0]


def _assert_anchored_here(finding):
    assert finding.file == THIS_FILE, finding
    assert isinstance(finding.line, int) and finding.line > 0, finding
    assert f"{THIS_FILE}:{finding.line}" in finding.format()


# ---------------------------------------------------------------------------
# PTA090 — half-precision accumulation (historical-bug redo: the
# finding must name the offending dot eqn, anchored at the call site)
# ---------------------------------------------------------------------------

def test_pta090_bf16_accumulation_flagged():
    def f(x):
        return x @ x  # bf16 matmul, no f32 accumulator asked for

    rep = analysis.check(f, input_spec=[InputSpec([8, 8], "bfloat16")],
                         record=False)
    find = _only(rep, "PTA090")
    assert find.severity == "warning"
    assert "preferred_element_type" in find.message
    _assert_anchored_here(find)


def test_pta090_silent_with_f32_accumulator():
    import jax
    import jax.numpy as jnp

    def f(x):
        return jax.lax.dot_general(
            x._value, x._value, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    rep = analysis.check(f, input_spec=[InputSpec([8, 8], "bfloat16")],
                         record=False)
    assert "PTA090" not in _codes(rep)


# ---------------------------------------------------------------------------
# PTA091 — wide half-precision reductions (raw lax: jnp.sum upcasts)
# ---------------------------------------------------------------------------

def _raw_reduce(x):
    import jax

    return jax.lax.reduce_sum_p.bind(x._value, axes=(0,))


def test_pta091_wide_half_reduce_flagged():
    rep = analysis.check(_raw_reduce,
                         input_spec=[InputSpec([8192], "float16")],
                         record=False)
    find = _only(rep, "PTA091")
    assert "8192" in find.message and "float16" in find.message


def test_pta091_silent_below_threshold():
    rep = analysis.check(_raw_reduce,
                         input_spec=[InputSpec([128], "float16")],
                         record=False)
    assert "PTA091" not in _codes(rep)


# ---------------------------------------------------------------------------
# PTA092 — exp-family statistics in float16 (bf16 has f32's exponent
# range, so it is exempt by design)
# ---------------------------------------------------------------------------

def _exp_prog(x):
    import jax.numpy as jnp

    return jnp.exp(x._value)


def test_pta092_fp16_exp_flagged():
    rep = analysis.check(_exp_prog,
                         input_spec=[InputSpec([16], "float16")],
                         record=False)
    find = _only(rep, "PTA092")
    assert find.severity == "error"


def test_pta092_bf16_exp_clean():
    rep = analysis.check(_exp_prog,
                         input_spec=[InputSpec([16], "bfloat16")],
                         record=False)
    assert "PTA092" not in _codes(rep)


# ---------------------------------------------------------------------------
# PTA094 — the `1e-12` LayerNorm-eps-in-fp16 class (historical-bug
# redo: jax flushes the literal at trace time; the detector must still
# name the offending add, anchored in THIS file)
# ---------------------------------------------------------------------------

def test_pta094_fp16_eps_underflow_flagged():
    import jax.numpy as jnp

    def f(x):
        v = x._value
        return v / jnp.sqrt(jnp.var(v) + jnp.float16(1e-12))

    rep = analysis.check(f, input_spec=[InputSpec([32], "float16")],
                         record=False)
    find = _only(rep, "PTA094")
    assert find.severity == "error"
    assert "zero" in find.message
    _assert_anchored_here(find)


def test_pta094_silent_with_representable_eps():
    import jax.numpy as jnp

    def f(x):
        v = x._value
        return v / jnp.sqrt(jnp.var(v) + jnp.float16(1e-4))

    rep = analysis.check(f, input_spec=[InputSpec([32], "float16")],
                         record=False)
    assert "PTA094" not in _codes(rep)


# ---------------------------------------------------------------------------
# PTA095 — cast churn
# ---------------------------------------------------------------------------

def test_pta095_round_trip_cast_flagged():
    import jax.numpy as jnp

    def f(x):
        return x._value.astype(jnp.bfloat16).astype(jnp.float32)

    rep = analysis.check(f, input_spec=[InputSpec([8], "float32")],
                         record=False)
    find = _only(rep, "PTA095")
    assert "float32->bfloat16->float32" in find.message


def test_pta095_single_cast_clean():
    import jax.numpy as jnp

    def f(x):
        return x._value.astype(jnp.bfloat16)

    rep = analysis.check(f, input_spec=[InputSpec([8], "float32")],
                         record=False)
    assert "PTA095" not in _codes(rep)


# ---------------------------------------------------------------------------
# PTA093 — master-weightless fp16 training (build-time audit)
# ---------------------------------------------------------------------------

def _fp16_setup():
    model = nn.Linear(4, 2)
    paddle.amp.decorate(model, level="O2", dtype="float16")
    opt = optim.SGD(learning_rate=0.1,
                    parameters=model.parameters())
    x = paddle.to_tensor(np.random.rand(4, 4).astype(np.float16))
    y = paddle.to_tensor(np.zeros((4,), dtype="int64"))
    return model, opt, x, y


def test_pta093_masterless_fp16_build_aborts_armed():
    san.configure("numerics")
    model, opt, x, y = _fp16_setup()
    step = paddle.jit.TrainStepCompiler(model, opt,
                                        nn.CrossEntropyLoss())
    with pytest.raises(ValueError) as ei:
        step(x, y)
    msg = str(ei.value)
    assert "PTA093" in msg and "float16" in msg and "weight" in msg
    assert "PTA093" in {f.code for f in san.findings()}


def test_pta093_grad_scaler_is_the_clean_twin():
    san.configure("numerics")
    model, opt, x, y = _fp16_setup()
    step = paddle.jit.TrainStepCompiler(
        model, opt, nn.CrossEntropyLoss(),
        grad_scaler=paddle.amp.GradScaler(init_loss_scaling=1.0))
    step(x, y)  # builds and runs — no PTA093
    assert "PTA093" not in {f.code for f in san.findings()}


def test_pta093_multi_precision_is_the_other_clean_twin():
    san.configure("numerics")
    assert not precision.audit_train_precision(
        {"w": "float16"}, None, True)
    # bf16 is exempt by design (f32 exponent range)
    assert not precision.audit_train_precision(
        {"w": "bfloat16"}, None, False)


def test_pta093_disarmed_is_silent_and_counter_clean():
    assert not san.armed()
    before = {k: v for k, v in registry.snapshot().items()
              if k.startswith(("sanitize/", "analysis/PTA09"))}
    assert not precision.audit_train_precision(
        {"w": "float16"}, None, False)
    after = {k: v for k, v in registry.snapshot().items()
             if k.startswith(("sanitize/", "analysis/PTA09"))}
    assert after == before


# ---------------------------------------------------------------------------
# PTA092 — auto_cast white-list audit (armed raises, bf16 exempt)
# ---------------------------------------------------------------------------

def test_autocast_fp16_whitelisting_softmax_raises_armed():
    san.configure("numerics")
    with pytest.raises(ValueError) as ei:
        with paddle.amp.auto_cast(dtype="float16",
                                  custom_white_list=["softmax"]):
            pass
    assert "PTA092" in str(ei.value) and "softmax" in str(ei.value)


def test_autocast_bf16_whitelist_clean():
    san.configure("numerics")
    with paddle.amp.auto_cast(dtype="bfloat16",
                              custom_white_list=["softmax"]):
        pass
    assert "PTA092" not in {f.code for f in san.findings()}


# ---------------------------------------------------------------------------
# runtime numerics probe — overflow attribution + dump bundle
# ---------------------------------------------------------------------------

def test_probe_attributes_fp16_overflow_to_tensor(tmp_path,
                                                  monkeypatch):
    import jax.numpy as jnp

    from paddle_tpu.monitor import flight

    monkeypatch.setenv("PADDLE_FLIGHT_DIR", str(tmp_path))
    san.configure("numerics")
    model, opt, x, y = _fp16_setup()
    # inject the overflow: a weight near fp16 max saturates the
    # matmul and blows the grads to inf
    model.weight._value = jnp.full(tuple(model.weight.shape),
                                   60000.0, jnp.float16)
    step = paddle.jit.TrainStepCompiler(
        model, opt, nn.CrossEntropyLoss(),
        grad_scaler=paddle.amp.GradScaler(init_loss_scaling=1.0))
    step(x, y)
    msgs = [f.message for f in san.findings() if f.code == "PTA092"]
    assert any("param/weight" in m for m in msgs), msgs
    snap = registry.snapshot()
    assert snap.get("numerics/param/weight/saturated", 0) >= 1 \
        or snap.get("numerics/param/weight/nonfinite", 0) >= 1
    assert any(k.startswith("numerics/") and k.endswith("/absmax")
               for k in snap)
    # the dump bundle carries the probe's last-read stats, so a
    # post-mortem names the tensor
    path = flight.write_dump("numerics_probe")
    with open(path) as f:
        payload = json.load(f)
    assert payload["numerics"]["armed"] is True
    assert "param/weight" in payload["numerics"]["last"]
    kinds = [e["kind"] for e in flight.recorder.tail(256)]
    assert "sanitize_finding" in kinds


def test_grad_scaler_backoff_annotates_flight_timeline():
    from paddle_tpu.monitor import flight

    scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0,
                                   decr_every_n_nan_or_inf=1)
    scaler._record_step(True)  # one non-finite microstep verdict
    kinds = [e["kind"] for e in flight.recorder.tail(64)]
    assert "amp_scale_backoff" in kinds
    assert scaler.get_init_loss_scaling() == 512.0


def test_probe_scan_path_and_sample_cadence():
    san.configure("numerics:sample=2")
    assert num.sample_every() == 2
    model = nn.Linear(4, 2)
    opt = optim.SGD(learning_rate=0.1,
                    parameters=model.parameters())
    step = paddle.jit.TrainStepCompiler(model, opt,
                                        nn.CrossEntropyLoss(),
                                        steps_per_dispatch=2)
    x = paddle.to_tensor(
        np.random.rand(2, 4, 4).astype(np.float32))
    y = paddle.to_tensor(np.zeros((2, 4), dtype="int64"))
    losses = step(x, y)
    assert tuple(losses.shape) == (2,)
    step(x, y)
    d = num.describe()
    # every dispatch observes; the sample=2 cadence bounds host syncs
    assert d["observations"] == 2 and d["sample"] == 2
    assert any(k.startswith("param/") for k in d["last"])


# ---------------------------------------------------------------------------
# disarmed contract — bit-identical lowering, zero counters
# ---------------------------------------------------------------------------

def _zeroed_step():
    import jax.numpy as jnp

    model = nn.Linear(4, 2)
    for p in model.parameters():
        p._value = jnp.zeros_like(p._value)
    opt = optim.SGD(learning_rate=0.1,
                    parameters=model.parameters())
    return paddle.jit.TrainStepCompiler(model, opt,
                                        nn.CrossEntropyLoss())


def test_disarmed_lowering_bit_identical(hlo_sans_locations):
    x = paddle.to_tensor(np.ones((4, 4), np.float32))
    y = paddle.to_tensor(np.zeros((4,), dtype="int64"))
    plain1 = hlo_sans_locations(
        _zeroed_step().lower_compiled(x, y).as_text())
    plain2 = hlo_sans_locations(
        _zeroed_step().lower_compiled(x, y).as_text())
    assert plain1 == plain2  # deterministic baseline, probe-free
    san.configure("numerics")
    armed = hlo_sans_locations(
        _zeroed_step().lower_compiled(x, y).as_text())
    assert armed != plain1  # the probe only exists when armed


def test_disarmed_dispatch_zero_numerics_counters():
    assert not san.armed()
    step = _zeroed_step()
    x = paddle.to_tensor(np.random.rand(4, 4).astype(np.float32))
    y = paddle.to_tensor(np.zeros((4,), dtype="int64"))
    before = {k: v for k, v in registry.snapshot().items()
              if k.startswith("numerics/")}
    step(x, y)
    step(x, y)
    after = {k: v for k, v in registry.snapshot().items()
             if k.startswith("numerics/")}
    assert after == before
    assert step._numerics_built is False
    assert num.describe()["observations"] == 0


# ---------------------------------------------------------------------------
# spec grammar + CLI
# ---------------------------------------------------------------------------

def test_parse_spec_numerics_params():
    fams = san.parse_spec("numerics:sample=4:absmax=30000")
    assert fams == {"numerics": {"sample": 4.0, "absmax": 30000.0}}
    san.configure("numerics:absmax=30000")
    assert num.absmax_threshold() == 30000.0


def test_parse_spec_unknown_family_names_the_valid_ones():
    with pytest.raises(ValueError) as ei:
        san.parse_spec("numericz")
    msg = str(ei.value)
    assert "numericz" in msg and "numerics" in msg \
        and "donation" in msg


def test_numerics_env_params(monkeypatch):
    monkeypatch.setenv("PADDLE_NUMERICS_SAMPLE", "8")
    monkeypatch.setenv("PADDLE_NUMERICS_ABSMAX", "20000")
    san.configure("numerics")
    assert num.sample_every() == 8
    assert num.absmax_threshold() == 20000.0
    # the spec param wins over the env
    san.configure("numerics:sample=3")
    assert num.sample_every() == 3


def test_cli_sanitize_numerics_flags_seeded_file(tmp_path, capsys):
    from paddle_tpu.analysis.cli import main

    p = tmp_path / "m.py"
    p.write_text(
        "def norm_fp16(x, jnp):\n"
        "    h = x.astype('float16')\n"
        "    return rms(h, eps=1e-12)\n"
        "with auto_cast(dtype='float16',\n"
        "               custom_white_list=['softmax']):\n"
        "    pass\n")
    rc = main([str(p), "--sanitize", "numerics"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "PTA094" in out and "PTA092" in out
    # clean twin: f32 function with the same eps stays silent
    p.write_text("def norm(x):\n    return rms(x, eps=1e-12)\n")
    rc = main([str(p), "--sanitize", "numerics"])
    capsys.readouterr()
    assert rc == 0


def test_lint_numerics_source_direct():
    rep = precision.lint_numerics_source(
        "def f(x):\n"
        "    y = x.astype('float16')\n"
        "    return norm(y, epsilon=5e-9)\n", "t.py")
    find = _only(rep, "PTA094")
    assert find.line == 3
    # no fp16 mention -> the package's f32 eps defaults stay clean
    rep = precision.lint_numerics_source(
        "def f(x):\n    return norm(x, epsilon=5e-9)\n", "t.py")
    assert not rep.findings


# ---------------------------------------------------------------------------
# amp list audit — every entry must resolve against the live registry
# ---------------------------------------------------------------------------

def test_amp_lists_resolve_against_live_op_registry():
    stale = paddle.amp.audit_op_lists()
    assert stale == {"white": [], "black": []}, stale


def test_amp_white_list_has_no_predispatch_aliases():
    # mm/bmm delegate to matmul BEFORE dispatch — listing them would
    # be dead weight the audit exists to catch
    assert "mm" not in paddle.amp.WHITE_LIST
    assert "bmm" not in paddle.amp.WHITE_LIST
    assert "matmul" in paddle.amp.WHITE_LIST


# ---------------------------------------------------------------------------
# doc-drift gate — every registered PTA code has a README table row
# ---------------------------------------------------------------------------

def test_readme_documents_every_pta_code():
    import os

    from paddle_tpu.analysis.diagnostics import DIAGNOSTICS

    readme = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "README.md")
    with open(readme, encoding="utf-8") as f:
        text = f.read()
    rows = set(re.findall(r"^\|\s*`?(PTA\d{3})`?\s*\|", text, re.M))
    codes = set(DIAGNOSTICS)
    assert codes - rows == set(), \
        f"codes missing a README table row: {sorted(codes - rows)}"
    assert rows - codes == set(), \
        f"README rows for unregistered codes: {sorted(rows - codes)}"
    for code in ("PTA090", "PTA091", "PTA092", "PTA093", "PTA094",
                 "PTA095"):
        assert code in codes
