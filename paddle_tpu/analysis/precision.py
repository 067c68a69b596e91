"""PTA09x precision sanitizer (ISSUE 17) — the fp8-everywhere gate.

Static half: `analyze_precision` is a dtype-provenance dataflow pass
over `make_jaxpr` traces (the PR-2 walk machinery) that tells a
*correct* low-precision program from a silently-degrading one:

  * dot/conv on bf16/fp16 operands ACCUMULATING in low precision —
    no f32 `preferred_element_type`, the exact hazard the
    bf16·bf16→f32 panel regime forbids                    (PTA090)
  * wide reductions (sum/cumsum folding >= a size threshold) carried
    out in half precision — bf16's 8 mantissa bits lose integer
    exactness past 256, fp16's 11 past 2048               (PTA091)
  * exp-family range statistics computed in float16 — e^x saturates
    past |x|≈11 (f16 max 65504), where float32/bf16 reach ≈88
                                                          (PTA092)
  * fp16 master-weightless training: float16 trainable parameters
    stepped without a GradScaler or fp32 master weights — runtime
    audit at the TrainStepCompiler build, like PTA006     (PTA093)
  * eps/literal constants that underflow to zero or denormal in the
    value's dtype (the `1e-12` LayerNorm-eps-in-fp16 class: jax
    flushes the literal at trace time, so the jaxpr leg detects the
    resulting zero-literal feeding a sqrt/rsqrt/div)      (PTA094)
  * cast churn: A→B→A convert round-trips that cost bytes (and, when
    B is narrower, precision) for nothing — perf lint     (PTA095)

Runtime half (armed by `PADDLE_SANITIZE=numerics`, report-only under
`PADDLE_ANALYSIS=1`): `audit_train_precision` at the train-step build
and `audit_autocast` at `amp.auto_cast` entry RAISE on error findings
under the sanitizer, report under analysis, and stay silent (counter-
clean) disarmed — the same contract as the PTA08x guards. The
per-tensor stats probe itself lives in `monitor/numerics.py`.

`lint_numerics_source` is the CLI `--sanitize numerics` AST leg: it
needs no trace, so it only flags what source text can prove — tiny
eps literals in fp16-touching functions, and float16 autocasts that
white-list range-sensitive (BLACK_LIST-class) ops.
"""
from __future__ import annotations

import ast
import math

import numpy as np
import jax
from jax.extend import core as jex_core

from .diagnostics import Report, Severity
from .jaxpr import (_LOW, _Capped, TracedProgram, eqn_anchor,
                    _subjaxprs)
from .preflight import _walk_no_nested_defs

__all__ = ["analyze_precision", "audit_train_precision",
           "audit_autocast", "lint_numerics_source"]

# PTA090: accumulation-carrying primitives
_ACCUM_PRIMS = ("dot_general", "conv_general_dilated")
# PTA091: folding reductions (jnp.sum/mean auto-upcast half inputs to
# f32, so a low-dtype reduce here is the lax-level / hand-rolled kind)
_REDUCE_PRIMS = ("reduce_sum", "cumsum")
_REDUCE_ELEMS = 4096
# PTA092: range-sensitive transcendentals — float16 only (bfloat16
# shares float32's exponent range, saturation is not its failure mode)
_EXP_PRIMS = ("exp", "expm1", "log", "log1p", "logistic")
# PTA094: ops whose literal operand is an eps-class constant
_EPS_CARRIERS = ("add", "sub", "max", "min")
# ... flagged only when the result feeds one of these (the
# `x / sqrt(var + eps)` idiom) — an unconditional `+ 0.0` (e.g. the
# scale kernel's default bias) is not an underflow bug
_EPS_CONSUMERS = ("sqrt", "rsqrt", "log", "pow", "integer_pow")


def _each_jaxpr(jaxpr):
    """Every (sub-)jaxpr, outermost first — producer/consumer maps
    are per-level (vars don't cross jaxpr boundaries by identity)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _each_jaxpr(sub)


def _dtype_of(v):
    try:
        return str(v.aval.dtype)
    except Exception:
        return ""


def _scalar_literal(v):
    """float value of a scalar jax Literal operand, else None."""
    if not isinstance(v, jex_core.Literal):
        return None
    val = np.asarray(v.val)
    if val.size != 1 or not np.issubdtype(val.dtype, np.floating):
        return None
    return float(val.reshape(()))


def _reduced_elems(eqn):
    """How many elements one output element folds together."""
    shape = tuple(getattr(eqn.invars[0].aval, "shape", ()) or ())
    if eqn.primitive.name == "reduce_sum":
        axes = eqn.params.get("axes", ())
        return int(math.prod(shape[a] for a in axes)) if axes else 1
    if eqn.primitive.name == "cumsum":
        ax = eqn.params.get("axis", 0)
        return int(shape[ax]) if shape else 1
    return 1


def analyze_precision(tp: TracedProgram, report: Report,
                      reduce_elems=_REDUCE_ELEMS):
    """PTA090/091/092/094/095 over one traced program."""
    cap = _Capped(report, "precision")
    for jaxpr in _each_jaxpr(tp.closed.jaxpr):
        producers = {}
        consumers = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not isinstance(v, jex_core.Literal):
                    consumers.setdefault(v, []).append(eqn)
            for v in eqn.outvars:
                producers[v] = eqn
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _ACCUM_PRIMS:
                _check_accum(eqn, cap, tp)
            elif name in _REDUCE_PRIMS:
                _check_reduce(eqn, cap, tp, reduce_elems)
            elif name in _EXP_PRIMS:
                _check_exp(eqn, cap, tp)
            elif name == "convert_element_type":
                _check_churn(eqn, producers, cap, tp)
            if name in _EPS_CARRIERS or name == "div":
                _check_eps(eqn, consumers, cap, tp)
    cap.flush()
    return report


def _check_accum(eqn, cap, tp):
    """PTA090: dot/conv whose operands AND result are low-precision
    floats — the MXU-style f32 accumulator was never asked for."""
    in_dts = {_dtype_of(v) for v in eqn.invars}
    out_dt = _dtype_of(eqn.outvars[0])
    if not (in_dts & set(_LOW)) or out_dt not in _LOW:
        return
    file, line = eqn_anchor(eqn, tp.anchor)
    low = sorted(in_dts & set(_LOW))[0]
    cap.add("PTA090",
            f"{eqn.primitive.name} on {low} operands accumulates in "
            f"{out_dt} — long contractions lose mantissa bits every "
            "partial sum; pass preferred_element_type=float32 (the "
            "bf16*bf16->f32 panel contract) and cast the result",
            file=file, line=line, severity=Severity.WARNING)


def _check_reduce(eqn, cap, tp, threshold):
    """PTA091: a genuinely-half-precision wide reduction (jnp.sum and
    friends upcast automatically; this is the hand-rolled kind)."""
    dt = _dtype_of(eqn.invars[0])
    if dt not in _LOW:
        return
    n = _reduced_elems(eqn)
    if n < threshold:
        return
    file, line = eqn_anchor(eqn, tp.anchor)
    cap.add("PTA091",
            f"{eqn.primitive.name} folds {n} elements in {dt} — "
            f"half-precision partial sums stop being exact past "
            f"{'2048' if dt == 'float16' else '256'} same-magnitude "
            "addends; accumulate in float32 and cast the result",
            file=file, line=line, severity=Severity.WARNING)


def _check_exp(eqn, cap, tp):
    """PTA092: exp-family statistics in float16 (saturation past
    |x|≈11; float32/bfloat16 reach ≈88)."""
    dt = _dtype_of(eqn.invars[0])
    if dt != "float16":
        return
    file, line = eqn_anchor(eqn, tp.anchor)
    cap.add("PTA092",
            f"{eqn.primitive.name} computed in float16 — e^x "
            "overflows float16 past x≈11.09 (max 65504) and "
            "underflows past x≈-17; compute softmax/logsumexp/norm "
            "statistics in float32 (or bfloat16) and cast after",
            file=file, line=line, severity=Severity.ERROR)


def _check_eps(eqn, consumers, cap, tp):
    """PTA094: a literal that is zero or denormal in the operand's
    low-precision dtype. jax flushes `f16_x + 1e-12` to `add x 0.0`
    at trace time, so the zero case only fires when the result feeds
    a sqrt/rsqrt/log/pow/div — the guard-eps idiom, where a flushed
    eps means div-by-zero at runtime."""
    for i, v in enumerate(eqn.invars):
        lit = _scalar_literal(v)
        if lit is None:
            continue
        dt = _dtype_of(v)
        if dt not in _LOW:
            continue
        tiny = float(np.finfo(np.dtype(dt)).tiny)
        denormal = 0.0 < abs(lit) < tiny
        zero_div = (lit == 0.0 and eqn.primitive.name == "div"
                    and i == 1)
        zero_eps = (lit == 0.0 and eqn.primitive.name in _EPS_CARRIERS
                    and _feeds_eps_consumer(eqn, consumers))
        if not (denormal or zero_div or zero_eps):
            continue
        file, line = eqn_anchor(eqn, tp.anchor)
        if denormal:
            msg = (f"literal {lit!r} is DENORMAL in {dt} (normal min "
                   f"{tiny:.3g}) — gradual underflow costs precision "
                   "and flushes to zero on flush-to-zero hardware; "
                   "use an eps the dtype can represent (>= "
                   f"{tiny:.3g}) or compute the guard in float32")
        else:
            msg = (f"literal constant flushed to zero in {dt} at "
                   f"trace time (the `1e-12` LayerNorm-eps class: "
                   f"{dt} underflows below "
                   f"{np.finfo(np.dtype(dt)).smallest_subnormal:.3g})"
                   " — the guarded sqrt/div now divides by exactly "
                   "zero; use a representable eps or an f32 guard")
        cap.add("PTA094", msg, file=file, line=line,
                severity=Severity.ERROR)
        return


def _feeds_eps_consumer(eqn, consumers):
    out = eqn.outvars[0]
    for user in consumers.get(out, ()):
        name = user.primitive.name
        if name in _EPS_CONSUMERS:
            return True
        if name == "div" and len(user.invars) > 1 \
                and user.invars[1] is out:
            return True
    return False


def _check_churn(eqn, producers, cap, tp):
    """PTA095: convert(convert(x, A->B), B->A) — a cast round-trip.
    B narrower than A destroys mantissa bits silently; B wider is
    pure byte churn. Either way the inner cast bought nothing."""
    src = eqn.invars[0]
    if isinstance(src, jex_core.Literal):
        return
    inner = producers.get(src)
    if inner is None or inner.primitive.name != "convert_element_type":
        return
    a = _dtype_of(inner.invars[0])
    b = _dtype_of(inner.outvars[0])
    c = _dtype_of(eqn.outvars[0])
    dts = (a, b, c)
    if a != c or a == b or not all(
            d.startswith(("float", "bfloat")) for d in dts):
        return
    file, line = eqn_anchor(eqn, tp.anchor)
    lossy = b in _LOW and a not in _LOW
    cap.add("PTA095",
            f"cast round-trip {a}->{b}->{a}: "
            + ("the narrowing leg silently destroyed mantissa bits "
               "the widening leg cannot restore"
               if lossy else "two converts that cancel — pure "
               "bandwidth churn")
            + "; drop the round-trip (or keep the narrow value if "
            "the truncation was the point)",
            file=file, line=line, severity=Severity.WARNING)


# ---------------------------------------------------------------------------
# runtime half (gated like the PTA08x guards: sanitize raises,
# analysis reports, disarmed stays counter-clean)
# ---------------------------------------------------------------------------

def _emit_or_raise(code, msg):
    from ..monitor import sanitize as _sanitize

    armed = _sanitize._numerics
    if not armed:
        from . import enabled as _analysis_enabled

        if not _analysis_enabled():
            return False
    from ..monitor.sanitize import _emit

    _emit(code, msg)
    if armed:
        raise ValueError(f"{code} {msg}")
    return True


def audit_train_precision(param_dtypes, grad_scaler, multi_precision,
                          where="train_step"):
    """PTA093 at the TrainStepCompiler build: float16 trainable
    parameters stepped with neither a GradScaler (gradients underflow
    unscaled) nor fp32 master weights (updates below the fp16 ulp are
    lost forever). bfloat16 is exempt — its f32 exponent range makes
    scaling optional (the repo's bf16-first stance). Raises under
    PADDLE_SANITIZE=numerics, reports under PADDLE_ANALYSIS=1."""
    fp16 = sorted(n for n, dt in param_dtypes.items()
                  if dt == "float16")
    if not fp16 or grad_scaler is not None or multi_precision:
        return False
    return _emit_or_raise(
        "PTA093",
        f"{where}: {len(fp16)} float16 trainable parameter(s) (e.g. "
        f"{fp16[0]!r}) trained without a GradScaler or fp32 master "
        "weights — gradients underflow unscaled and sub-ulp updates "
        "vanish; pass grad_scaler=GradScaler() or "
        "optimizer(multi_precision=True)")


def audit_autocast(dtype, custom_white_list, where="auto_cast"):
    """PTA092 at `amp.auto_cast` entry: a float16 autocast whose
    custom_white_list force-lowers range-sensitive (BLACK_LIST-class)
    ops — the exact saturation the black list exists to prevent."""
    if str(dtype) not in ("float16", "fp16"):
        return False
    from .. import amp as _amp

    risky = sorted(set(custom_white_list or ()) & _amp.BLACK_LIST)
    if not risky:
        return False
    return _emit_or_raise(
        "PTA092",
        f"{where}: float16 autocast white-lists range-sensitive "
        f"op(s) {risky} — e^x saturates float16 past x≈11; keep "
        "exp/softmax/norm statistics out of the fp16 white list")


# ---------------------------------------------------------------------------
# CLI AST leg (`--sanitize numerics`)
# ---------------------------------------------------------------------------

# smallest positive float16 subnormal — an eps below this is ZERO in
# fp16; the static leg only flags it in fp16-touching functions, so
# the package's own f32 `epsilon=1e-12` defaults stay clean
_FP16_FLUSH = 2.0 ** -24
_EPS_KWARGS = ("eps", "epsilon")


def _mentions_fp16(node):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str) \
                and sub.value in ("float16", "fp16", "half"):
            return True
        if isinstance(sub, (ast.Name, ast.Attribute)):
            name = sub.id if isinstance(sub, ast.Name) else sub.attr
            if name in ("float16", "fp16", "half"):
                return True
    return False


def _literal_float(node):
    if isinstance(node, ast.Constant) and isinstance(
            node.value, (int, float)) and not isinstance(
            node.value, bool):
        return float(node.value)
    return None


def lint_numerics_source(source, filename="<string>", report=None):
    """AST pass over one file: fp16-underflowing eps kwargs (PTA094)
    and float16 autocasts white-listing range-sensitive ops
    (PTA092)."""
    report = report if report is not None else Report()
    try:
        tree = ast.parse(source, filename=filename)
    except SyntaxError:
        return report

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and _mentions_fp16(node):
            _lint_fp16_eps(node, report, filename)
        if isinstance(node, ast.Call):
            _lint_autocast_call(node, report, filename)
    return report


def _lint_fp16_eps(fdef, report, filename):
    for sub in _walk_no_nested_defs(fdef):
        if not isinstance(sub, ast.Call):
            continue
        for kw in sub.keywords:
            if kw.arg not in _EPS_KWARGS:
                continue
            v = _literal_float(kw.value)
            if v is None or not 0.0 < v < _FP16_FLUSH:
                continue
            report.add(
                "PTA094",
                f"{fdef.name}: {kw.arg}={v!r} underflows to ZERO in "
                f"float16 (flush bound {_FP16_FLUSH:.3g}) — this "
                "fp16-touching function would divide by an "
                "eps-less denominator; use >= 1e-7 or an f32 guard",
                file=filename, line=sub.lineno,
                severity=Severity.ERROR, analyzer="precision")


def _autocast_kwargs(call):
    name = ""
    f = call.func
    if isinstance(f, ast.Attribute):
        name = f.attr
    elif isinstance(f, ast.Name):
        name = f.id
    if name not in ("auto_cast", "amp_guard"):
        return None, ()
    dtype, white = None, ()
    for kw in call.keywords:
        if kw.arg == "dtype" and isinstance(kw.value, ast.Constant):
            dtype = kw.value.value
        if kw.arg == "custom_white_list" and isinstance(
                kw.value, (ast.List, ast.Tuple, ast.Set)):
            white = tuple(e.value for e in kw.value.elts
                          if isinstance(e, ast.Constant)
                          and isinstance(e.value, str))
    return dtype, white


def _lint_autocast_call(call, report, filename):
    dtype, white = _autocast_kwargs(call)
    if dtype not in ("float16", "fp16") or not white:
        return
    from .. import amp as _amp

    risky = sorted(set(white) & _amp.BLACK_LIST)
    if risky:
        report.add(
            "PTA092",
            f"float16 auto_cast white-lists range-sensitive op(s) "
            f"{risky} — e^x saturates float16 past x≈11; keep "
            "exp/softmax/norm statistics in float32",
            file=filename, line=call.lineno,
            severity=Severity.ERROR, analyzer="precision")
