"""Jaxpr analyzers: abstract-trace a paddle-level callable and lint
the resulting program.

The trace mirrors `jit.StaticFunction._build` (params temporarily
bound to tracers, trace_mode on, rng key pushed) but lowers through
`jax.make_jaxpr` instead of `jax.jit`, so analysis sees the SAME
program the compiler would build — dtype flow, captured constants,
dead ops and comm primitives included — without compiling or running
anything.
"""
from __future__ import annotations

import inspect
import os

import numpy as np
import jax
from jax import tree_util
from jax.extend import core as jex_core

from ..core import engine
from ..core.tensor import Tensor
from .diagnostics import Report, Severity

__all__ = ["trace_program", "iter_eqns", "eqn_anchor", "fn_anchor",
           "analyze_dtypes", "analyze_consts", "analyze_dead",
           "analyze_tracer_leaks", "analyze_static_args"]

# noisy programs repeat one defect many times; cap per-code spam
_MAX_PER_CODE = 8

# TPU-hostile wide dtypes (PTA001)
_WIDE = ("float64", "complex128")
# PTA002: implicit upcasts that silently discard mixed-precision wins
_LOW = ("bfloat16", "float16")
_HIGH = ("float32", "float64")


def fn_anchor(fn):
    """(file, line) of a callable's def site — the fallback anchor."""
    try:
        target = inspect.unwrap(fn)
        if not (inspect.isfunction(target) or inspect.ismethod(target)):
            target = getattr(target, "forward", None) or \
                getattr(target, "__call__", target)
        file = inspect.getsourcefile(target)
        _, line = inspect.getsourcelines(target)
        return file, line
    except (OSError, TypeError):
        return None, None


# frames inside the framework's dispatch/kernel layers are never the
# anchor the user needs — the call SITE above them is. Model-code
# packages (vision/text/hapi) stay anchorable: the self-audit traces
# our own models and should point INTO them.
_PKG_DIR = os.path.dirname(os.path.dirname(__file__))
_DISPATCH_DIRS = tuple(
    os.path.join(_PKG_DIR, d)
    for d in ("core", "ops", "analysis", "jit", "nn", "distributed",
              "amp", "static")) + (
    os.path.join(_PKG_DIR, "__init__.py"),)


def eqn_anchor(eqn, default=(None, None)):
    """(file, line) of the frame that emitted this eqn, from jax
    source_info: the innermost frame outside the framework's dispatch
    layers, so `x + y` in a model anchors at the model line, not at
    engine.apply_op; falls back to the innermost frame, then to the
    function's def site."""
    from jax._src import source_info_util as siu

    frames = list(siu.user_frames(eqn.source_info.traceback))
    for frame in frames:
        if not str(frame.file_name).startswith(_DISPATCH_DIRS):
            return frame.file_name, frame.start_line
    if frames:
        return frames[0].file_name, frames[0].start_line
    return default


class TracedProgram:
    """Trace result handed to the analyzers."""

    def __init__(self, closed, fn, statics, params, input_dtypes=(),
                 pre_leak_sites=()):
        self.closed = closed          # ClosedJaxpr
        self.fn = fn
        self.statics = statics        # non-tensor leaves of the call
        self.params = params          # Layer parameters traced as args
        # dtypes as DECLARED (InputSpec / arg values) — jax
        # canonicalizes float64 away under x64-off, so the jaxpr
        # can't witness a wide-dtype spec; this can
        self.input_dtypes = tuple(input_dtypes)
        # tracer-holding sites that existed BEFORE this trace (stale
        # leaks from earlier traces) — not this function's doing
        self.pre_leak_sites = frozenset(pre_leak_sites)
        self.anchor = fn_anchor(fn)


def _example_from_spec(input_spec):
    """InputSpecs -> concrete-shape avals: symbolic/None dims become a
    probe batch of 2 (analysis runs outside any jax.export symbolic
    scope, and 2 flushes out dim-0 broadcasting accidents that a batch
    of 1 would hide)."""
    from ..jit import _specs_to_avals

    avals = []
    for a in _specs_to_avals(input_spec):
        shape = tuple(int(d) if isinstance(d, (int, np.integer)) else 2
                      for d in a.shape)
        avals.append(jax.ShapeDtypeStruct(shape, a.dtype))
    return avals


def trace_program(fn, input_spec=None, example=None):
    """Abstractly trace `fn` and return a TracedProgram.

    Either `input_spec` (list[InputSpec] — positional tensor args) or
    `example` ((args, kwargs) with Tensor leaves, e.g. a real call's
    arguments at `to_static` build time) must be given.
    """
    from ..jit import StaticFunction, _collect_layers
    from ..nn import Layer
    from ..ops import random as _random
    from ..jit import state as _jstate

    if isinstance(fn, StaticFunction):
        fn = fn.dygraph_function
    collect_target = fn.forward if isinstance(fn, Layer) else fn

    if example is not None:
        args, kwargs = example
        flat, treedef = tree_util.tree_flatten(
            (tuple(args), dict(kwargs or {})),
            is_leaf=lambda x: isinstance(x, Tensor))
        tensor_pos = [i for i, a in enumerate(flat)
                      if isinstance(a, Tensor)]
        statics = [None if isinstance(a, Tensor) else a for a in flat]
        avals = [jax.ShapeDtypeStruct(tuple(flat[i].shape),
                                      flat[i]._value.dtype)
                 for i in tensor_pos]
        example_tensors = [flat[i] for i in tensor_pos]
    elif input_spec is not None:
        avals = _example_from_spec(input_spec)
        n = len(avals)
        flat = [None] * n
        treedef = tree_util.tree_structure(
            (tuple(flat), {}), is_leaf=lambda x: x is None)
        tensor_pos = list(range(n))
        statics = [None] * n
        example_tensors = []
    else:
        raise ValueError(
            "analysis.trace_program needs input_spec or example args "
            "to know the tensor shapes/dtypes to trace with")

    layers = _collect_layers(collect_target, example_tensors)
    if isinstance(fn, Layer) and fn not in layers:
        layers.insert(0, fn)
    params = []
    for lay in layers:
        params.extend(p for _, p in lay.named_parameters())
        params.extend(b for _, b in lay.named_buffers())
    pvals = [jax.ShapeDtypeStruct(tuple(p._value.shape),
                                  p._value.dtype) for p in params]

    # folded EAGERLY so the pushed key is a plain constant: inside
    # make_jaxpr it would be a (usually dead) fold_in eqn polluting
    # the dead-computation analysis
    folded_key = jax.random.fold_in(_random._rng.base, 0)

    def traced(pv, av):
        with engine.trace_mode():
            prev_key = _random.push_traced_key(folded_key)
            saved = []
            try:
                for p, v in zip(params, pv):
                    saved.append((p, p._value))
                    p._value = v
                leaves = list(statics)
                for i, pos in enumerate(tensor_pos):
                    leaves[pos] = Tensor(av[i], stop_gradient=True,
                                         _internal=True)
                cargs, ckwargs = tree_util.tree_unflatten(treedef,
                                                          leaves)
                # pop in a finally: analysis-trace failures are an
                # expected, swallowed path (trace_build_hook never
                # raises) — leaking the scope would pin dead tracers
                # on the jit thread-local stack for process lifetime
                scope = _jstate.push_buffer_scope()
                try:
                    out = fn(*cargs, **ckwargs)
                finally:
                    _jstate.pop_buffer_scope()
                flat_out, _ = tree_util.tree_flatten(
                    out, is_leaf=lambda x: isinstance(x, Tensor))
                # buffer updates (BatchNorm stats) ARE outputs of the
                # real compiled program (StaticFunction._build returns
                # new_bufs) — dropping them here would make every
                # running-stat update chain look like dead computation
                buf_outs = [nv._value for (_, nv) in scope]
                return [o._value if isinstance(o, Tensor) else o
                        for o in flat_out] + buf_outs
            finally:
                for p, v in saved:
                    p._value = v
                _random.pop_traced_key(prev_key)

    input_dtypes = [str(a.dtype) for a in avals]
    pre_sites = _leak_sites(fn)
    closed = jax.make_jaxpr(traced)(pvals, avals)
    return TracedProgram(closed, fn, statics, params,
                         input_dtypes=input_dtypes,
                         pre_leak_sites=pre_sites)


def _subjaxprs(v):
    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for e in v:
            yield from _subjaxprs(e)


def iter_eqns(jaxpr):
    """All eqns, recursing into call/branch/loop sub-jaxprs (pjit,
    cond branches, scan/while bodies, shard_map ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


class _Capped:
    """Per-code finding cap: analyzers on a 10k-eqn program must not
    emit 10k copies of one defect."""

    def __init__(self, report, analyzer):
        self._report = report
        self._analyzer = analyzer
        self._n = {}

    def add(self, code, message, file=None, line=None, severity=None):
        n = self._n.get(code, 0)
        self._n[code] = n + 1
        if n < _MAX_PER_CODE:
            self._report.add(code, message, file=file, line=line,
                             severity=severity, analyzer=self._analyzer)

    def flush(self):
        for code, n in self._n.items():
            if n > _MAX_PER_CODE:
                self._report.add(
                    code, f"... and {n - _MAX_PER_CODE} more "
                    f"{code} sites (capped)", severity=Severity.INFO,
                    analyzer=self._analyzer)


def _aval_dtype(v):
    try:
        return str(v.aval.dtype)
    except Exception:
        return ""


def analyze_dtypes(tp: TracedProgram, report: Report):
    """PTA001 float64/complex128 anywhere in the traced program (input
    avals, captured consts, op results); PTA002 implicit half->full
    precision upcasts via convert_element_type."""
    file, line = tp.anchor
    cap = _Capped(report, "dtype")
    jaxpr = tp.closed.jaxpr
    for i, dt in enumerate(tp.input_dtypes):
        if dt in _WIDE:
            cap.add("PTA001",
                    f"traced input #{i} is declared {dt} — TPUs "
                    "execute float64 in software emulation (or "
                    "reject it); declare the InputSpec as "
                    "float32/bfloat16",
                    file=file, line=line)
    for c in tp.closed.consts:
        dt = str(getattr(c, "dtype", ""))
        if dt in _WIDE:
            cap.add("PTA001",
                    f"captured constant has dtype {dt} "
                    f"(shape {tuple(getattr(c, 'shape', ()))})",
                    file=file, line=line)
    for eqn in iter_eqns(jaxpr):
        # anchor resolution walks the source-info traceback — only
        # pay for it when a finding actually fires
        for v in eqn.outvars:
            dt = _aval_dtype(v)
            if dt in _WIDE:
                efile, eline = eqn_anchor(eqn, tp.anchor)
                cap.add("PTA001",
                        f"op {eqn.primitive.name} produces {dt}",
                        file=efile, line=eline)
                break
        if eqn.primitive.name == "convert_element_type":
            old = _aval_dtype(eqn.invars[0])
            new = str(eqn.params.get("new_dtype", ""))
            if old in _LOW and new in _HIGH:
                efile, eline = eqn_anchor(eqn, tp.anchor)
                cap.add("PTA002",
                        f"implicit promotion {old} -> {new}: a "
                        "mixed-precision value is upcast mid-program "
                        "(dtype-mismatched operands?); the matmul/"
                        "reduce after it runs full-width",
                        file=efile, line=eline)
    cap.flush()
    return report


def analyze_consts(tp: TracedProgram, report: Report,
                   threshold=1 << 20):
    """PTA003: host constants baked into the program above `threshold`
    bytes — each one is re-uploaded with every executable and bloats
    both the HLO and device memory (const-capture bloat)."""
    file, line = tp.anchor
    cap = _Capped(report, "const")
    for c in tp.closed.consts:
        shape = getattr(c, "shape", None)
        dtype = getattr(c, "dtype", None)
        if shape is None or dtype is None:
            continue
        try:
            nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        except Exception:
            continue
        if nbytes >= threshold:
            cap.add("PTA003",
                    f"host constant of {nbytes} bytes (shape "
                    f"{tuple(shape)}, {dtype}) is baked into the "
                    "traced program — pass it as an input or "
                    "register it as a buffer/Parameter",
                    file=file, line=line)
    cap.flush()
    return report


def analyze_dead(tp: TracedProgram, report: Report):
    """PTA004: eqns whose outputs reach no program output and that
    carry no effect — computation XLA will DCE, which usually means a
    forgotten return value or a stale code path."""
    jaxpr = tp.closed.jaxpr
    live = {v for v in jaxpr.outvars
            if isinstance(v, jex_core.Var)}
    dead = []
    for eqn in reversed(jaxpr.eqns):
        outs = [v for v in eqn.outvars
                if not isinstance(v, jax.core.DropVar)]
        if any(v in live for v in outs) or eqn.effects:
            for v in eqn.invars:
                if isinstance(v, jex_core.Var):
                    live.add(v)
        elif eqn_anchor(eqn)[0] != __file__:
            # eqns anchored in THIS file are the trace harness's own
            # (the pushed rng key) — dead by construction, not a
            # finding about the user's program
            dead.append(eqn)
    if dead:
        dead.reverse()
        file, line = eqn_anchor(dead[0], tp.anchor)
        names = [e.primitive.name for e in dead[:6]]
        report.add(
            "PTA004",
            f"{len(dead)} op(s) compute values no output uses "
            f"(first: {', '.join(names)}) — dead computation traced "
            "into the program",
            file=file, line=line, analyzer="dead")
    return report


def _holds_tracer(v, depth=2):
    if isinstance(v, jax.core.Tracer):
        return True
    if isinstance(v, Tensor):
        return isinstance(v._value, jax.core.Tracer)
    if depth <= 0:
        return False
    try:
        if isinstance(v, dict):
            return any(_holds_tracer(x, depth - 1) for x in v.values())
        if isinstance(v, (list, tuple, set)):
            return any(_holds_tracer(x, depth - 1) for x in v)
    except Exception:
        pass
    return False


def _leak_sites(fn):
    """Names of tracer-holding sites reachable from fn's globals,
    closure cells and bound instance."""
    target = getattr(fn, "forward", fn)
    target = getattr(target, "__func__", target)
    sites = []
    glb = getattr(target, "__globals__", None)
    if isinstance(glb, dict):
        mod = glb.get("__name__", "")
        for name, v in list(glb.items()):
            if _holds_tracer(v):
                sites.append(f"global {mod}.{name}")
    closure = getattr(target, "__closure__", None) or ()
    for i, cell in enumerate(closure):
        try:
            if _holds_tracer(cell.cell_contents):
                names = getattr(target.__code__, "co_freevars", ())
                nm = names[i] if i < len(names) else f"cell#{i}"
                sites.append(f"closure variable {nm!r}")
        except ValueError:
            pass
    owner = getattr(fn, "__self__", None) or (
        fn if not inspect.isroutine(fn) else None)
    if owner is not None and hasattr(owner, "__dict__"):
        for name, v in list(vars(owner).items()):
            if _holds_tracer(v):
                sites.append(f"attribute "
                             f"{type(owner).__name__}.{name}")
    return sites


def analyze_tracer_leaks(tp: TracedProgram, report: Report):
    """PTA005: after the trace finished, a tracer is NEWLY reachable
    from the function's globals, closure cells or bound instance —
    the classic leak that explodes later as UnexpectedTracerError (or
    silently pins the whole trace in memory). Sites that already held
    tracers before the trace (someone else's stale leak) are
    excluded."""
    file, line = tp.anchor
    new = [s for s in _leak_sites(tp.fn)
           if s not in tp.pre_leak_sites]
    for site in new[:_MAX_PER_CODE]:
        report.add(
            "PTA005",
            f"a tracer escaped the trace into {site} — the stored "
            "value is a symbolic placeholder, not data; any later "
            "use raises UnexpectedTracerError",
            file=file, line=line, analyzer="leak")
    return report


def analyze_static_args(statics, report: Report, anchor=(None, None)):
    """PTA006 recompile hazards, classified by the SAME freeze path
    `jit` uses for its cache key (`_freeze_static_ex`): an `id`
    fallback means two equal-content args compile twice (and a reused
    id can collide); `pickled` means every cache probe pays a pickle;
    a bare Python float is usually data that should be a traced
    tensor (every new value = a full recompile)."""
    from ..jit import _freeze_static_ex

    file, line = anchor
    cap = _Capped(report, "static")
    for i, v in enumerate(statics):
        if v is None:
            continue
        desc = f"static arg #{i} ({type(v).__name__})"
        try:
            _, kind = _freeze_static_ex(v, memoize=False)
        except Exception:
            continue
        if kind == "id":
            cap.add("PTA006",
                    f"{desc} is unhashable and unpicklable — the jit "
                    "cache keys it by id(), so equal-content values "
                    "recompile and a recycled id silently collides",
                    file=file, line=line, severity=Severity.ERROR)
        elif kind == "pickled":
            cap.add("PTA006",
                    f"{desc} is unhashable — every call pickles it to "
                    "build the cache key; make it hashable (tuple, "
                    "frozen dataclass) or pass it as a tensor",
                    file=file, line=line)
        elif kind == "ndarray":
            cap.add("PTA006",
                    f"{desc} is a numpy array used as a STATIC arg — "
                    "content-digested per object; pass it as a traced "
                    "tensor unless the program genuinely specializes "
                    "on its values",
                    file=file, line=line, severity=Severity.INFO)
        elif isinstance(v, float):
            cap.add("PTA006",
                    f"{desc} is a Python float — each distinct value "
                    "compiles a fresh program; pass it as a 0-d "
                    "tensor if it varies per step (lr, temperature)",
                    file=file, line=line)
    cap.flush()
    return report
