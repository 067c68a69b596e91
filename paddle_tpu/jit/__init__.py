"""paddle.jit — dygraph→static compilation.

Parity target: @to_static / ProgramTranslator
(python/paddle/fluid/dygraph/dygraph_to_static/program_translator.py:775,
fluid/dygraph/jit.py).

TPU-native design: instead of AST rewriting into a Program, the
function is *traced with jax*: parameters' storage is temporarily bound
to tracers, the same Python code runs, and the result is one XLA
computation. `jax.jit` caches per input signature — the analog of
ConcreteProgram caching per InputSpec. `TrainStepCompiler` additionally
closes the loop: forward+backward+optimizer update in ONE compiled,
buffer-donated XLA program (the fastest possible step on TPU).
"""
from __future__ import annotations

import functools
import inspect
import time as _time
import weakref

import numpy as np
import jax
import jax.numpy as jnp
from jax import tree_util

from ..core import engine
from ..core import monitor as _monitor
from ..core.tensor import Tensor
from ..monitor import chaos as _chaos
from ..monitor import flight as _flight
from ..monitor import perf as _perf
from ..monitor import sanitize as _sanitize
from ..ops import random as _random
from . import state as _jstate
from .program import Program, specialised

__all__ = ["to_static", "not_to_static", "save", "load", "TracedLayer",
           "TrainStepCompiler", "InputSpec", "set_max_loop_iterations",
           "cache_report", "Program"]

from .dy2static import set_max_loop_iterations  # noqa: E402


class InputSpec:
    """reference: python/paddle/static/input.py InputSpec."""

    def __init__(self, shape=None, dtype="float32", name=None):
        self.shape = shape
        self.dtype = dtype
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


def _collect_layers(func, args):
    """Find Layer objects whose parameters the traced fn may touch."""
    from ..nn import Layer

    layers = []
    seen = set()

    def add(obj):
        if isinstance(obj, Layer) and id(obj) not in seen:
            seen.add(id(obj))
            layers.append(obj)

    add(getattr(func, "__self__", None))
    if inspect.isfunction(func) or inspect.ismethod(func):
        closure = getattr(func, "__closure__", None)
        if closure:
            for cell in closure:
                try:
                    add(cell.cell_contents)
                except ValueError:
                    pass
        code = getattr(func, "__code__", None)
        glb = getattr(func, "__globals__", {})
        if code is not None:
            for name in code.co_names:
                if name in glb:
                    add(glb[name])
    for a in args:
        add(a)
    return layers


_digest_cache = {}  # id(arr) -> (weakref, digest); bounded
_DIGEST_CACHE_MAX = 64


def _digest_cache_evict_one():
    """Make room for one entry: drop a dead-weakref entry if any,
    else the oldest (first-inserted — dicts preserve insertion
    order). The old overflow behavior cleared the WHOLE memo, which
    re-hashed every live static table on the next call."""
    dead = next((k for k, (wr, _) in _digest_cache.items()
                 if wr() is None), None)
    _digest_cache.pop(dead if dead is not None
                      else next(iter(_digest_cache)))
    _monitor.stat_add("jit/digest_cache/evictions", 1)


def _freeze_static_ex(v, memoize=True):
    """(cache key, kind) for a static (non-Tensor) argument; kind in
    {"hashable", "ndarray", "pickled", "id"} — the classification
    `analysis` reports recompile hazards from (PTA006), off the SAME
    code path jit keys its program cache with.

    Arrays hash by CONTENT digest — repr() truncates big arrays and
    would silently collide distinct values into one compiled program.
    Digests memoize per array object (weakly) so a large static table
    is hashed once, not on every call; in-place mutation of a static
    arg after first use is not supported (jax's own static-arg
    contract). `memoize=False` (analysis probes) skips the memo so
    probing never evicts a hot entry."""
    try:
        hash(v)
        return v, "hashable"
    except TypeError:
        pass
    if isinstance(v, np.ndarray):
        import hashlib

        ent = _digest_cache.get(id(v))
        if ent is not None and ent[0]() is v:
            return ent[1], "ndarray"
        key = ("ndarray", v.shape, str(v.dtype),
               hashlib.sha256(np.ascontiguousarray(v).tobytes())
               .digest())
        if memoize:
            try:
                if len(_digest_cache) >= _DIGEST_CACHE_MAX:
                    _digest_cache_evict_one()
                _digest_cache[id(v)] = (weakref.ref(v), key)
            except TypeError:
                pass
        return key, "ndarray"
    try:
        import hashlib
        import pickle

        return ("pickled",
                hashlib.sha256(pickle.dumps(v)).digest()), "pickled"
    except Exception:
        return ("id", id(v)), "id"


def _freeze_static(v):
    return _freeze_static_ex(v)[0]


from .dy2static import source_calls_grad as _source_calls_grad  # noqa: E402


# every live compiled callable (StaticFunction / TrainStepCompiler),
# weakly held — cache_report() walks it so hang/crash dump bundles can
# show WHAT was compiled and which signatures each cache holds
_live_compiled = weakref.WeakSet()


_CACHE_REPORT_MAX_KEYS = 16


def cache_report():
    """Per-compiled-callable program-cache summary (entry counts + a
    short repr of the first few cache keys). The flight-recorder dump
    bundles (monitor.flight.write_dump) embed this so a post-mortem
    can spot recompile storms — dozens of keys differing in one
    shape/static arg — without rerunning anything. The key list is
    capped: in the storm case `entries` carries the signal, and a
    thousand 200-char reprs would bloat every bundle the watchdog
    writes mid-incident."""
    out = []
    for obj in list(_live_compiled):
        try:
            if isinstance(obj, StaticFunction):
                keys = list(obj._compiled.keys())
                progs = [obj._compiled[k][0]
                         for k in keys[:_CACHE_REPORT_MAX_KEYS]]
                out.append({"kind": "to_static",
                            "fn": obj._telemetry_key,
                            "entries": len(keys),
                            "keys": [repr(k)[:200] for k in
                                     keys[:_CACHE_REPORT_MAX_KEYS]],
                            # per-entry memory_analysis() byte dicts,
                            # aligned with "keys" (None where capture
                            # was off/failed) — the HBM-footprint leg
                            # of an OOM post-mortem
                            "memory": [p.memory for p in progs],
                            # per-entry cost_analysis() dicts, same
                            # alignment — the roofline ledger's
                            # bundle-portable copy (monitor perf
                            # reads these offline)
                            "cost": [p.cost for p in progs]})
            elif isinstance(obj, TrainStepCompiler):
                prog = obj._program
                out.append({"kind": "train_step",
                            "fn": type(obj._model).__name__,
                            "entries": int(prog is not None),
                            "steps": obj._step,
                            "steps_per_dispatch":
                                getattr(obj, "_steps_per_dispatch", 1),
                            "memory": prog and prog.memory,
                            "cost": prog and prog.cost})
        except Exception:
            pass  # a half-torn-down object must not break a dump
    out.sort(key=lambda d: (d["kind"], d["fn"]))
    return out


def _telemetry_name(func):
    """Low-cardinality but unambiguous jit counter key: the last two
    __qualname__ components minus '<locals>', so Model.forward and
    OtherModel.forward get distinct jit/… namespaces (bare __name__
    aggregated every 'forward' into one counter) while module-level
    functions keep their plain name."""
    qn = (getattr(func, "__qualname__", None)
          or getattr(func, "__name__", None) or "fn")
    parts = [p for p in qn.split(".") if p != "<locals>"]
    return ".".join(parts[-2:])


class StaticFunction:
    """Compiled wrapper (reference: StaticFunction,
    program_translator.py:236)."""

    def __init__(self, func, input_spec=None, build_strategy=None,
                 backend=None):
        self._func = func
        # dy2static AST pass: rewrite data-dependent if/while into
        # lax.cond/while_loop converter calls (reference
        # ProgramTranslator AST transformers); falls back to trace-only
        # conversion when the source can't be transformed
        from .dy2static import ast_transform

        # for_call=True: a function with no control flow of its own
        # still transforms so conversion reaches its CALLEES (reference
        # convert_call_func.py recursion — r4)
        self._trace_target = ast_transform(func, for_call=True) or func
        # grad-inside-to_static (reference grad_transformer): tape
        # recording during tracing is opt-in per function — detected
        # from the source so ordinary traces don't pay the vjp cost
        self._needs_tape = _source_calls_grad(func)
        self._input_spec = input_spec
        self._compiled = {}  # cache key -> (Program, output box)
        # computed once — __call__ is the per-train-step hot path
        self._telemetry_key = _telemetry_name(func)
        _live_compiled.add(self)
        functools.update_wrapper(self, func,
                                 assigned=("__name__", "__doc__"))

    def __get__(self, instance, owner):
        if instance is None:
            return self
        bound = StaticFunction.__new__(StaticFunction)
        bound._func = self._func.__get__(instance, owner)
        bound._trace_target = self._trace_target.__get__(instance, owner) \
            if self._trace_target is not self._func else bound._func
        bound._input_spec = self._input_spec
        bound._compiled = self._compiled  # shared: ONE cache
        bound._needs_tape = self._needs_tape
        bound._telemetry_key = self._telemetry_key
        functools.update_wrapper(bound, bound._func,
                                 assigned=("__name__", "__doc__"))
        return bound

    @property
    def dygraph_function(self):
        return self._func

    def __call__(self, *args, **kwargs):
        from ..nn import Layer

        target = self._trace_target
        layers = _collect_layers(self._func, args)
        params = []
        for lay in layers:
            params.extend(p for _, p in lay.named_parameters())
            params.extend(b for _, b in lay.named_buffers())
        param_ids = [id(p) for p in params]

        flat_args, args_treedef = tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        tensor_pos = [i for i, a in enumerate(flat_args)
                      if isinstance(a, Tensor)]
        static_leaves = [None if isinstance(a, Tensor) else a
                         for a in flat_args]

        from .dy2static import max_loop_iterations

        # stop_gradient travels into the trace: paddle.grad INSIDE a
        # to_static function (reference grad_transformer) needs the
        # differentiable args to record tape edges; it changes the
        # traced program, so it joins the cache key
        arg_sg = tuple(bool(flat_args[i].stop_gradient)
                       for i in tensor_pos)
        key = (args_treedef, tuple(tensor_pos),
               tuple((tuple(flat_args[i].shape), str(flat_args[i].dtype))
                     for i in tensor_pos), tuple(param_ids), arg_sg,
               tuple(_freeze_static(v) for v in static_leaves),
               # the loop bound changes the lowering (while_loop vs
               # bounded scan) — it must participate in the cache key
               # or a later set_max_loop_iterations() silently reuses
               # the stale compiled program
               max_loop_iterations())
        fname = self._telemetry_key
        entry = self._compiled.get(key)
        if entry is None:
            # opt-in static analysis at build time (PADDLE_ANALYSIS=1,
            # gated inside the hook): preflight + jaxpr lint of the
            # about-to-compile program; purely observational — never
            # alters the trace below, never raises
            from ..analysis import trace_build_hook

            trace_build_hook(target, args=args, kwargs=kwargs,
                             where=f"to_static:{fname}")
            # shape-specialized entries of one fn share its counters
            # (the family) and keep gauges of their own: entry 0 the
            # plain name, later ones "#n", n the entry's position in
            # _compiled — the index program_footprints() derives
            # bundle names from
            jfn, box = self._build(target, params, args_treedef,
                                   tensor_pos, static_leaves, arg_sg)
            entry = self._compiled[key] = (
                Program(jfn, specialised(fname, len(self._compiled)),
                        family=fname), box)
        # the first dispatch traces and compiles (jax.jit is lazy):
        # the Program counts it a miss, spans it `compile/<fn>` and
        # keeps its wall time out of the dispatch histogram below
        prog, box = entry
        arg_ts = [flat_args[i] for i in tensor_pos]
        rngc = jnp.asarray(_random._rng.counter, jnp.uint32)
        requires = engine.is_grad_enabled() \
            and not engine.in_trace_mode() \
            and (any(not p.stop_gradient for p in params)
                 or any(not t.stop_gradient for t in arg_ts))
        timing = _perf.dispatch_timing_enabled()
        t_d0 = _time.perf_counter()
        with prog.dispatch():
            if requires:
                # differentiable boundary: the compiled forward is
                # one tape op, so loss.backward() after a @to_static
                # forward flows grads into params/inputs (reference:
                # ProgramTranslator builds the backward program for
                # the whole block)
                def kernel(pv, av, rc):
                    out_vals, new_bufs, _ = prog.bind(pv, av, rc)()
                    return tuple(out_vals), tuple(new_bufs)

                outs, buf_outs = engine.apply_op(
                    "run_program", kernel, list(params), arg_ts, rngc)
                out_vals = [o._value for o in outs]
                new_buf_vals = [nv._value for nv in buf_outs]
                flat_out = list(outs)
            else:
                out_vals, new_buf_vals, _ = prog.bind(
                    [p._value for p in params],
                    [t._value for t in arg_ts], rngc)()
                flat_out = [Tensor(v, stop_gradient=True,
                                   _internal=True) for v in out_vals]
        if not prog.compiled() and timing:
            # measured attribution leg of the roofline: wall time
            # blocked on the outputs (async dispatch returns futures
            # — an unblocked timer measures the enqueue)
            jax.block_until_ready(out_vals)
            _perf.observe_dispatch(
                fname, int((_time.perf_counter() - t_d0) * 1e6))
        _random._rng.counter += 1
        # commit buffer updates (BatchNorm stats)
        for (buf, _), nv in zip(box["buf_refs"], new_buf_vals):
            buf._value = nv
        # footprint capture only AFTER the first successful
        # execution: a user-code raise inside the first trace is the
        # call's own to report
        prog.capture()
        return tree_util.tree_unflatten(box["treedef"], flat_out)

    def _build(self, target, params, args_treedef, tensor_pos,
               static_leaves, arg_sg=None):
        """The function one cache entry jits (a Program's), and the
        box its trace fills with the output tree and the buffers it
        updated."""
        box = {}
        import contextlib

        tape_ctx = (engine.trace_tape if self._needs_tape
                    else contextlib.nullcontext)

        def jfn(pvals, avals, rng_counter):
            with engine.trace_mode(), tape_ctx():
                prev_key = _random.push_traced_key(
                    jax.random.fold_in(_random._rng.base, rng_counter))
                try:
                    for p, v in zip(params, pvals):
                        p.__dict__["_saved_value"] = p._value
                        p._value = v
                    leaves = list(static_leaves)
                    for i, pos in enumerate(tensor_pos):
                        sg = True if arg_sg is None else arg_sg[i]
                        leaves[pos] = Tensor(avals[i], stop_gradient=sg,
                                             _internal=True)
                    args, kwargs = tree_util.tree_unflatten(args_treedef,
                                                            leaves)
                    scope = _jstate.push_buffer_scope()
                    out = target(*args, **kwargs)
                    _jstate.pop_buffer_scope()
                    flat_out, treedef = tree_util.tree_flatten(
                        out, is_leaf=lambda x: isinstance(x, Tensor))
                    out_vals = [o._value if isinstance(o, Tensor) else o
                                for o in flat_out]
                    box["treedef"] = treedef
                    box["buf_refs"] = scope
                    new_bufs = [nv._value for (_, nv) in scope]
                    return out_vals, new_bufs, {}
                finally:
                    for p in params:
                        sv = p.__dict__.pop("_saved_value", None)
                        if sv is not None:
                            p._value = sv
                    _random.pop_traced_key(prev_key)

        return jfn, box

    def concrete_program(self):
        return None


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Decorator/wrapper compiling a dygraph callable with XLA."""
    from ..nn import Layer

    def decorate(fn):
        if isinstance(fn, Layer):
            fn.forward = StaticFunction(fn.forward, input_spec)
            return fn
        return StaticFunction(fn, input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(func):
    func._not_to_static = True
    return func


class TracedLayer:
    """reference: fluid/dygraph/jit.py TracedLayer — trace once, run
    the compiled function, optionally export for inference."""

    def __init__(self, layer, static_fn, input_spec):
        self._layer = layer
        self._static_fn = static_fn
        self._input_spec = input_spec

    @staticmethod
    def trace(layer, inputs):
        from ..nn import Layer

        fn = layer.forward if isinstance(layer, Layer) else layer
        sf = fn if isinstance(fn, StaticFunction) else StaticFunction(fn)
        out = sf(*inputs)
        spec = [InputSpec(shape=list(i.shape), dtype=str(i.dtype))
                for i in inputs if isinstance(i, Tensor)]
        return out, TracedLayer(layer, sf, spec)

    def __call__(self, *args):
        return self._static_fn(*args)

    def save_inference_model(self, path, feed=None, fetch=None):
        save(self._layer, path, input_spec=self._input_spec)


def _specs_to_avals(specs):
    """InputSpecs -> ShapeDtypeStructs; None/-1 dims become ONE shared
    symbolic dim (the batch) across ALL inputs — a single symbolic
    scope, since jax.export rejects mixing scopes (reference analog:
    TRT dynamic-shape profiles)."""
    from jax import export as jexport

    from ..core.dtype import convert_dtype

    sym = None
    avals = []
    for spec in specs:
        shape = list(spec.shape if spec.shape is not None else [])
        if any(d in (None, -1) for d in shape):
            if sym is None:
                sym = jexport.symbolic_shape("_pb")[0]
            shape = [sym if d in (None, -1) else int(d) for d in shape]
        avals.append(jax.ShapeDtypeStruct(
            tuple(shape), convert_dtype(spec.dtype or "float32")))
    return avals


def save(layer, path, input_spec=None, **configs):
    """jit.save — serialize the traced computation (jax.export /
    StableHLO) + parameters, reloadable WITHOUT the Python class.

    Parity: reference jit.save writes Program + params
    (fluid/dygraph/jit.py); here the "Program" is the exported
    StableHLO module (path.pdmodel) and params/buffers are
    path.pdiparams. The module is portable across processes and
    compiled by XLA at load time (serialized per-chip executables are
    not portable across runtime versions, StableHLO is).
    """
    import os
    import pickle

    from jax import export as jexport

    from .. import framework
    from ..nn import Layer

    target = layer.forward if isinstance(layer, Layer) else layer
    if isinstance(target, StaticFunction):
        if input_spec is None:
            input_spec = target._input_spec
        target = target.dygraph_function
    if input_spec is None:
        raise ValueError("jit.save needs input_spec (shapes/dtypes of "
                         "the forward inputs) to trace the model")
    # resolve the Layer that owns the params: the layer itself, or the
    # bound instance of a plain/StaticFunction method
    owner = layer if isinstance(layer, Layer) else getattr(
        target, "__self__", None)
    if isinstance(owner, Layer):
        params = dict(owner.named_parameters())
        bufs = dict(owner.named_buffers())
    else:
        params, bufs = {}, {}  # pure function of its inputs
    was_training = getattr(owner, "training", False)
    if isinstance(owner, Layer):
        owner.eval()  # inference graph: no dropout
    p_items = list(params.items())
    b_items = list(bufs.items())
    box = {}

    def fn(pvals, bvals, *avals):
        with engine.trace_mode():
            saved = []
            try:
                for (k, p) in p_items + b_items:
                    saved.append((p, p._value))
                for (k, p) in p_items:
                    p._value = pvals[k]
                for (k, b) in b_items:
                    b._value = bvals[k]
                args = [Tensor(a, stop_gradient=True, _internal=True)
                        for a in avals]
                out = target(*args)
                flat, treedef = tree_util.tree_flatten(
                    out, is_leaf=lambda x: isinstance(x, Tensor))
                box["treedef"] = treedef
                return [o._value if isinstance(o, Tensor) else o
                        for o in flat]
            finally:
                for p, v in saved:
                    p._value = v

    avals = _specs_to_avals(input_spec)
    pvals = {k: jax.ShapeDtypeStruct(p._value.shape, p._value.dtype)
             for k, p in p_items}
    bvals = {k: jax.ShapeDtypeStruct(b._value.shape, b._value.dtype)
             for k, b in b_items}
    exported = jexport.export(jax.jit(fn))(pvals, bvals, *avals)
    if isinstance(owner, Layer) and was_training:
        owner.train()

    write_saved_artifacts(
        path, exported, params, bufs,
        {"out_treedef": box["treedef"],
         "input_spec": [(s.shape, str(s.dtype)) for s in input_spec],
         "class": type(layer).__name__})


def write_saved_artifacts(path, exported, params, buffers, meta):
    """Single writer for the saved-model triple (.pdmodel serialized
    StableHLO, .pdiparams params/buffers, .pdmeta pickle) — shared by
    jit.save and static.save_inference_model so the on-disk contract
    that jit.load/TranslatedLayer reads has exactly one producer."""
    import os
    import pickle

    from .. import framework

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".pdmodel", "wb") as f:
        f.write(exported.serialize())
    framework.save({"params": dict(params), "buffers": dict(buffers)},
                   path + ".pdiparams")
    with open(path + ".pdmeta", "wb") as f:
        pickle.dump(meta, f)


class TranslatedLayer:
    """Runnable loaded model (reference: fluid/dygraph/io.py
    TranslatedLayer) — calls the deserialized StableHLO program; the
    original Python class is not needed."""

    def __init__(self, exported, params, buffers, out_treedef,
                 input_spec=None):
        self._exported = exported
        self._params = params
        self._buffers = buffers
        self._out_treedef = out_treedef
        self._input_spec = input_spec or []
        self.training = False

    def __call__(self, *inputs):
        return self.forward(*inputs)

    def forward(self, *inputs):
        pvals = {k: v._value if isinstance(v, Tensor) else v
                 for k, v in self._params.items()}
        bvals = {k: v._value if isinstance(v, Tensor) else v
                 for k, v in self._buffers.items()}
        avals = [i._value if isinstance(i, Tensor) else jnp.asarray(i)
                 for i in inputs]
        flat = self._exported.call(pvals, bvals, *avals)
        out = [Tensor(v, stop_gradient=True, _internal=True)
               for v in flat]
        return tree_util.tree_unflatten(self._out_treedef, out)

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("TranslatedLayer is inference-only (the "
                           "exported program has no backward)")

    def state_dict(self):
        sd = dict(self._params)
        sd.update(self._buffers)
        return sd

    def parameters(self):
        return list(self._params.values())


def load(path, **configs):
    """jit.load — rebuild a runnable layer from jit.save artifacts."""
    import pickle

    from jax import export as jexport

    from .. import framework

    with open(path + ".pdmodel", "rb") as f:
        exported = jexport.deserialize(f.read())
    state = framework.load(path + ".pdiparams")
    with open(path + ".pdmeta", "rb") as f:
        meta = pickle.load(f)
    return TranslatedLayer(exported, state["params"], state["buffers"],
                           meta["out_treedef"],
                           input_spec=meta.get("input_spec"))


class TrainStepCompiler:
    """Whole-train-step compiler: loss_fn(model outputs) + optimizer
    update in one donated XLA program. This is the TPU performance
    path — analog of CompiledProgram+fused optimizer in the reference
    (compiler.py, ParallelExecutor), but stronger: fwd+bwd+update fuse.

    usage:
        step = TrainStepCompiler(model, opt, loss_fn)
        loss = step(x, y)          # updates model params in place

    steps_per_dispatch=K fuses K train steps into ONE dispatched XLA
    program (lax.scan carrying the donated params/opt-state): callers
    pass each batch element with a leading K axis of stacked
    microbatches and get back the K per-microstep losses. One host
    round-trip then amortizes over K steps — the whole-training-loop-
    on-device move of the Julia-to-TPU work, bounded to K so the host
    keeps its callback/logging cadence.
    """

    def __init__(self, model, optimizer, loss_fn=None, donate=True,
                 accumulate_steps=1, amp_level=None, amp_dtype="bfloat16",
                 amp_custom_white_list=None, amp_custom_black_list=None,
                 steps_per_dispatch=1, guard_nonfinite=False,
                 grad_scaler=None):
        """accumulate_steps > 1 enables gradient merge (reference:
        fleet gradient_merge_optimizer / RecomputeOptimizer micro-batch
        accumulation): grads from k consecutive calls accumulate in a
        donated buffer sharded like the parameter, and the optimizer
        applies the averaged gradient on every k-th call.

        amp_level="O1" wraps the traced forward in amp.auto_cast so
        allow-listed ops run in `amp_dtype` (reference amp_optimizer O1
        cast insertion, contrib/mixed_precision/decorator.py); "O2" is
        handled outside via amp.decorate on the model.

        steps_per_dispatch > 1 scans K microbatches through one
        program; the learning rate is sampled ONCE per dispatch (the
        same value a sequential loop that doesn't call scheduler.step()
        between microsteps would see), and rng counters advance per
        microstep so dropout/random streams match K separate calls.

        guard_nonfinite=True fuses an all-finite predicate over the
        loss and every gradient INTO the donated program (reference:
        check_finite_and_unscale): a tripped microstep skips the
        optimizer apply and passes params/opt-state/accumulators/
        buffers through bit-identically to never having run the batch
        (the non-finite loss is still returned so callers can see it).
        Under gradient merge (accumulate_steps>1) a tripped microstep
        instead contributes ZERO gradient to its window while the
        accumulate/apply/zero cadence runs on schedule — skipping the
        boundary would roll the window's grads into the next one and
        double-weight it. Trips count under train/nonfinite_skips and
        leave nonfinite_skip flight events. Reading the trip flags
        costs one small device sync per dispatch.

        grad_scaler=amp.GradScaler wires dynamic loss scaling through
        the compiled step (reference update_loss_scaling): the live
        scale rides in as a host scalar per dispatch (like lr — no
        recompile on backoff/growth), the loss is scaled before the
        backward and gradients unscale before the guard + apply, and
        each microstep's finite/non-finite verdict drives the scaler's
        backoff/growth accounting host-side. Implies
        guard_nonfinite."""
        self._model = model
        self._opt = optimizer
        self._loss_fn = loss_fn
        self._donate = donate
        self._amp_level = amp_level
        self._amp_dtype = amp_dtype
        self._amp_white = amp_custom_white_list
        self._amp_black = amp_custom_black_list
        self._accum_steps = max(1, int(accumulate_steps))
        self._steps_per_dispatch = max(1, int(steps_per_dispatch))
        # GradScaler(enable=False) is a no-op on the eager path —
        # honor the same contract here (its _scale is still 2**16;
        # baking it into the program would scale the loss AND force
        # the guard on for a scaler the user explicitly disabled)
        if grad_scaler is not None and not grad_scaler.is_enable():
            grad_scaler = None
        self._grad_scaler = grad_scaler
        self._guard_nonfinite = bool(guard_nonfinite
                                     or grad_scaler is not None)
        self.last_skips = 0  # nonfinite trips in the last dispatch
        # PADDLE_SANITIZE=numerics: set at build time iff the stats
        # probe was fused into the program (the dispatch path must
        # match the arity the BUILD chose, not the current arming)
        self._numerics_built = False
        self._accum_state = None
        # comm-compression state (distributed.compress): the
        # error-feedback residual buffers, donated like opt/accum
        # state. {} on every uncompressed step — an empty pytree adds
        # no inputs, so the lowered program is unchanged
        self._comm_state = None
        self._compress = None  # set by DistributedTrainStepCompiler
        self._program = None  # the jitted step (jit.Program), by _build
        self._names = None
        self._opt_state = None
        self._step = 0
        # the program's name, shared by its gauges and the dispatch
        # histogram: model class (compilers over different model
        # CLASSES must not share one gauge — the last one compiled
        # would overwrite the others' footprints) + fused dispatch
        # width K (Model.fit's fused K-step program and its K=1 tail
        # sibling are live together; the tail compiles last and would
        # overwrite the fused footprint with a ~K-times-smaller one).
        # Two instances of the SAME class at the same K still share a
        # gauge (last writer wins) — deliberate: per-instance names
        # would grow the persistent registry unboundedly across a
        # sweep's recompiles, and the bundle path
        # (program_footprints) keeps every live footprint via its
        # "(n)" suffixing, so dumps never lose one
        self._perf_name = f"train_step:{type(model).__name__}"
        if self._steps_per_dispatch != 1:
            self._perf_name += f"@k{self._steps_per_dispatch}"
        self._restored_opt = None    # elastic-checkpoint preload
        self._restored_accum = None  # (applied at first build)
        self._restored_comm = None
        _live_compiled.add(self)

    def _params_and_buffers(self):
        params = dict(self._model.named_parameters())
        bufs = dict(self._model.named_buffers())
        trainable = {k: p for k, p in params.items() if p.trainable}
        frozen = {k: p for k, p in params.items() if not p.trainable}
        return trainable, frozen, bufs

    # -- placement hooks (overridden by DistributedTrainStepCompiler) --
    def _prepare_call(self, trainable, frozen, bufs):
        pass

    def _place_batch(self, batch):
        return tuple(b._value if isinstance(b, Tensor) else jnp.asarray(b)
                     for b in batch)

    def _jit_step(self, step_fn, trainable, frozen, bufs, batch,
                  **shardings):
        """The step's Program: its counters and compile span are the
        family `train_step`'s, its gauges `self._perf_name`'s."""
        # argnums (0, 1, 2, 3): params, optimizer slots, grad-merge
        # accumulators, comm-compression residuals
        donate = (0, 1, 2, 3) if self._donate else ()
        return Program(step_fn, self._perf_name, donate_argnums=donate,
                       family="train_step", **shardings)

    def lower_compiled(self, *batch):
        """Build + lower + compile the step WITHOUT executing it —
        the auto-parallel planner reads `cost_analysis()` off the
        result (per-device flops/bytes of the partitioned module)."""
        trainable, frozen, bufs = self._params_and_buffers()
        self._prepare_call(trainable, frozen, bufs)
        if self._program is None:
            self._build(trainable, frozen, bufs, batch)
        pvals = {k: p._value for k, p in trainable.items()}
        fvals = {k: p._value for k, p in frozen.items()}
        bvals = {k: b._value for k, b in bufs.items()}
        avals = self._place_batch(batch)
        lr = np.float32(self._opt.get_lr())
        rngc = np.uint32(self._step)
        return self._program.lower(
            pvals, self._opt_state, self._accum_state,
            self._comm_state, fvals, bvals, avals, lr, rngc,
            self._loss_scale()).compile()

    def _loss_scale(self):
        """The host-scalar loss scale this dispatch runs at (1.0
        without a grad scaler — the trace multiplies by it only when a
        scaler is attached, so the plain program is untouched)."""
        s = self._grad_scaler
        return np.float32(s._scale if s is not None else 1.0)

    def _check_microbatch_axis(self, batch):
        """steps_per_dispatch=K expects every batch element stacked
        with a leading K axis — a wrong-shaped batch would otherwise
        scan garbage microbatches silently."""
        k = self._steps_per_dispatch
        if k <= 1:
            return
        for i, b in enumerate(batch):
            shape = np.shape(b._value if isinstance(b, Tensor) else b)
            if len(shape) < 1 or shape[0] != k:
                raise ValueError(
                    f"steps_per_dispatch={k}: batch element {i} must "
                    f"carry a leading axis of {k} stacked microbatches,"
                    f" got shape {tuple(shape)}")

    def __call__(self, *batch):
        """One dispatch, as the span `train/step` (id `step`). Its
        children say what the host was doing: `train/prepare` (here,
        and again in _run_compiled), `train/enqueue`, `train/block`
        (a wait span: monitor.flight.wait_span), `train/finish`; on the first call `compile/train_step` around
        the first dispatch, then `compile/capture/<program>`."""
        with _flight.span("train/step", step=self._step):
            return self._step_call(batch)

    def _step_call(self, batch):
        with _flight.span("train/prepare"):
            self._check_microbatch_axis(batch)
            trainable, frozen, bufs = self._params_and_buffers()
            self._prepare_call(trainable, frozen, bufs)
        if self._program is None:
            # opt-in analysis of the model forward about to be fused
            # into the step (PADDLE_ANALYSIS=1, gated inside the
            # hook) — observational only. Batch elements are placed
            # on device as traced inputs by _place_batch — mirror
            # that, not the to_static static-arg contract
            from ..analysis import trace_build_hook

            fwd_args = (batch[:-1] if self._loss_fn is not None
                        and len(batch) > 1 else batch)
            trace_build_hook(self._model, args=fwd_args,
                             where="train_step",
                             arrays_as_tensors=True)
            self._build(trainable, frozen, bufs, batch)
        # the first dispatch traces + XLA-compiles the whole fused
        # step: the Program counts it under jit/train_step/... and
        # spans it, prepare and finish included
        with self._program.dispatch():
            out = self._run_compiled(trainable, frozen, bufs, batch)
        self._program.capture()
        return out

    def _run_compiled(self, trainable, frozen, bufs, batch):
        with _flight.span("train/prepare"):
            # chaos site "dispatch": a synthetic RESOURCE_EXHAUSTED
            # here exercises the real OOM-forensics path
            # (is_oom_error classifies by exception NAME + message)
            if _chaos._armed:
                _chaos.hit("dispatch", steps=self._steps_per_dispatch)
            pvals = {k: p._value for k, p in trainable.items()}
            fvals = {k: p._value for k, p in frozen.items()}
            bvals = {k: b._value for k, b in bufs.items()}
            avals = self._place_batch(batch)
            # PTA04x donation sanitizer (PADDLE_SANITIZE=donation):
            # scan the dispatch inputs for already-deleted donated
            # buffers BEFORE XLA sees them — a stale reference fed
            # back in (the PR-8 clobbered-_jit_step shape) raises a
            # PTA041 report naming the donating dispatch instead of
            # the opaque "buffer has been deleted" crash
            san_site = None
            if _sanitize._donation:
                san_site = (f"train_step:{type(self._model).__name__}"
                            f" dispatch#{self._step}")
                _sanitize.check_args(
                    (pvals, self._opt_state, self._accum_state,
                     self._comm_state, fvals, bvals, avals),
                    site=san_site)
            # host scalars (jit globalizes them under any mesh/process
            # set)
            lr = np.float32(self._opt.get_lr())
            rngc = np.uint32(self._step)
            prev_opt, prev_acc = self._opt_state, self._accum_state
            prev_comm = self._comm_state
        timed = _perf.dispatch_timing_enabled()
        t_d0 = _time.perf_counter()
        with _flight.span("train/enqueue"):
            try:
                (new_p, new_opt, new_acc, new_comm, new_b, loss, skips,
                 nstats) = self._program.bind(
                    pvals, self._opt_state, self._accum_state,
                    self._comm_state, fvals, bvals, avals, lr, rngc,
                    self._loss_scale())()
                # a dispatch that compiled (the first; the second,
                # where the freshly initialized opt state's weak types
                # strengthen; a new batch shape) says so in the ring,
                # under this span
                compiled = self._program.compiled()
            except RuntimeError as e:
                if _sanitize._donation:
                    better = _sanitize.explain_deleted(
                        e, site=san_site or "train_step dispatch")
                    if better is not None:
                        raise better from e
                raise
        if _sanitize._donation and self._donate:
            # the program just donated argnums (0, 1, 2, 3): register
            # the OLD params/opt-state/accumulators/comm residuals
            # with this dispatch site so any later use of a retained
            # reference reports PTA041 with both ends named
            _sanitize.note_donated((pvals, prev_opt, prev_acc,
                                    prev_comm), site=san_site)
        if timed and not compiled:
            # measured roofline leg: block on the loss (the whole
            # program has executed once any output is ready) so the
            # histogram sees device time, not the async enqueue. One
            # ring event per dispatch feeds the StepTimer step-time
            # decomposition and the fleet straggler's top-span table.
            # A dispatch that compiled is skipped: a compile-laced
            # sample would poison the p99
            with _flight.wait_span("train/block"):
                jax.block_until_ready(loss)
            dus = int((_time.perf_counter() - t_d0) * 1e6)
            _perf.observe_dispatch(self._perf_name, dus)
            _flight.record("dispatch_end", name=self._perf_name,
                           dur_us=dus)
        with _flight.span("train/finish"):
            return self._finish_dispatch(
                trainable, bufs, new_p, new_opt, new_acc, new_comm,
                new_b, loss, skips, nstats)

    def _finish_dispatch(self, trainable, bufs, new_p, new_opt, new_acc,
                         new_comm, new_b, loss, skips, nstats):
        """Write the dispatch's state back and do its host-side
        accounting: what `train/finish` spans."""
        self._opt_state = new_opt
        self._accum_state = new_acc
        self._comm_state = new_comm
        for k, p in trainable.items():
            p._value = new_p[k]
        for k, b in bufs.items():
            b._value = new_b[k]
        kd = self._steps_per_dispatch
        # dispatch accounting: ONE host->device program launch just
        # covered kd train steps — bench reads these to attribute the
        # amortization win (acceptance: jit/dispatches == steps / K)
        _monitor.stat_add("jit/dispatches", 1)
        _monitor.stat_add("jit/steps", kd)
        if kd > 1:
            # gauge = width of the last FUSED dispatch; K=1 siblings
            # (fused-fit tails, ordinary configs in the same process)
            # must not overwrite it to 1 and erase the attribution —
            # jit/steps / jit/dispatches carries the exact ratio
            _monitor.stat_set("jit/steps_per_dispatch", kd)
            # the common K=1 path already leaves jit_cache_hit events;
            # only fused dispatches get their own ring entry
            _flight.record("jit_dispatch", steps=kd)
        prev = self._step
        self._step += kd
        # optimizer step count: how many k-th accumulation boundaries
        # the kd microsteps crossed (generalizes the old per-call
        # `step % accum == 0` check)
        self._opt._step_count += (self._step // self._accum_steps
                                  - prev // self._accum_steps)
        if self._guard_nonfinite:
            # the ONLY host sync the guard adds: kd tiny flags. Per-
            # microstep order matters to the scaler (a backoff between
            # microsteps of one dispatch can't retro-scale them — the
            # scale was sampled once, like lr — but the incr/decr
            # streak accounting must still see every verdict).
            flags = np.atleast_1d(np.asarray(skips))
            n = int(flags.sum())
            self.last_skips = n
            if n:
                _monitor.stat_add("train/nonfinite_skips", n)
                _flight.record("nonfinite_skip", steps=n,
                               dispatch_steps=kd)
            if self._grad_scaler is not None:
                for f in flags:
                    self._grad_scaler._record_step(bool(f))
        if self._numerics_built and nstats:
            # numerics probe host leg (PADDLE_SANITIZE=numerics):
            # observe() applies the sample=N cadence internally, so
            # only every Nth dispatch pays the tiny packed-stats sync
            from ..monitor import numerics as _numerics_mod

            _numerics_mod.observe(nstats, where=self._perf_name,
                                  step=prev)
        # K>1 returns the K per-microstep losses (shape (K,))
        return Tensor(loss, stop_gradient=True, _internal=True)

    def _init_opt_state(self, t_items):
        self._opt_state = self._opt.init_state(
            {k: p._value for k, p in t_items})
        # gradient-merge accumulation buffers (zeros, param-shaped)
        self._accum_state = (
            {k: jnp.zeros(p._value.shape, jnp.float32)
             for k, p in t_items}
            if self._accum_steps > 1 else {})
        self._comm_state = self._init_comm_state(t_items)

    def _init_comm_state(self, t_items):
        """Comm-compression state (error-feedback residuals). Base
        compiler: no mesh, nothing to compress — an empty pytree that
        leaves the lowered program untouched. Overridden by
        DistributedTrainStepCompiler."""
        return {}

    def restore_state(self, slots, step, accum=None, comm=None):
        """Preload optimizer state captured by an elastic checkpoint
        (incubate.checkpoint.elastic): `slots` is the host pytree
        {param_name: {slot: array}} a snapshot recorded off a live
        compiler's _opt_state (or the eager accumulators), `step` the
        global microstep counter (it seeds the per-dispatch rng
        fold-in, so bit-identical resume NEEDS it), `accum` the
        gradient-merge buffers mid-window, `comm` the quantized-
        collective error-feedback residuals (exact EF resume). The
        arrays are materialized — with this compiler's slot
        shardings, so a RESHAPED mesh re-shards them — when the step
        first builds; adopting a sibling's live state supersedes the
        preload."""
        self._restored_opt = {
            n: {s: np.asarray(v) for s, v in sl.items()}
            for n, sl in (slots or {}).items()}
        self._restored_accum = (
            {n: np.asarray(v) for n, v in accum.items()}
            if accum else None)
        self._restored_comm = (
            {n: np.asarray(v) for n, v in comm.items()}
            if comm else None)
        self._step = int(step)

    def _apply_restored_state(self):
        """Overwrite the freshly initialized (zeroed, sharded) opt/
        accum state with the checkpointed host arrays, placed onto
        each slot's existing sharding. Shape mismatches (a changed
        model) keep the fresh zeros for that slot."""
        restored, self._restored_opt = self._restored_opt, None
        for name, slots in restored.items():
            cur = self._opt_state.get(name)
            if cur is None:
                continue
            for sname, host in slots.items():
                ref = cur.get(sname)
                if ref is None:
                    cur[sname] = jnp.asarray(host)
                elif tuple(np.shape(host)) == tuple(np.shape(ref)):
                    cur[sname] = jax.device_put(
                        host.astype(ref.dtype), ref.sharding)
        racc, self._restored_accum = self._restored_accum, None
        if racc and self._accum_state:
            for name, host in racc.items():
                ref = self._accum_state.get(name)
                if ref is not None and tuple(np.shape(host)) == \
                        tuple(np.shape(ref)):
                    self._accum_state[name] = jax.device_put(
                        host.astype(ref.dtype), ref.sharding)
        rcomm, self._restored_comm = self._restored_comm, None
        if rcomm and self._comm_state:
            # a reshaped data axis changes the residual's per-rank
            # layout (leading dim = W): shape mismatches keep the
            # fresh zeros — bit-exact EF resume is a same-W contract
            for name, host in rcomm.items():
                ref = self._comm_state.get(name)
                if ref is not None and tuple(np.shape(host)) == \
                        tuple(np.shape(ref)):
                    self._comm_state[name] = jax.device_put(
                        host.astype(ref.dtype), ref.sharding)

    def adopt_state_from(self, other):
        """Take over `other`'s live optimizer/accumulator state and
        step counter. For two compilers over the SAME model/optimizer
        but different steps_per_dispatch (hapi's fused dispatch + its
        K=1 tail step): whichever ran last holds the canonical
        (possibly donated-and-replaced) arrays, so the next user must
        adopt before dispatching or it would feed stale — on TPU,
        already-donated — buffers back into its program."""
        if other is None or other._opt_state is None:
            return
        # live adopted state supersedes a checkpoint preload
        self._restored_opt = None
        self._restored_accum = None
        self._restored_comm = None
        self._opt_state = other._opt_state
        # comm residuals only transfer between same-policy siblings
        # (a differently-configured sibling's buffers have the wrong
        # shape/meaning — start fresh like a changed merge width)
        same_comm = getattr(other, "_compress", None) == self._compress
        if same_comm:
            self._comm_state = other._comm_state
        else:
            self._comm_state = self._init_comm_state(
                [(k, p) for k, p in self._model.named_parameters()
                 if p.trainable])
        if self._accum_steps == getattr(other, "_accum_steps", 1):
            self._accum_state = other._accum_state
        elif self._accum_steps > 1:
            # different merge width: the sibling's partial window
            # can't continue at this width — start a fresh one
            # (mirrors _init_opt_state's zeros)
            self._accum_state = {
                k: jnp.zeros(p._value.shape, jnp.float32)
                for k, p in self._model.named_parameters()
                if p.trainable}
        else:
            self._accum_state = {}
        self._step = other._step
        # _comm_shardings only when the residuals transferred too —
        # a different-policy sibling's layout describes ITS buffers
        attrs = ["_slot_shardings", "_accum_shardings"]
        if same_comm:
            attrs.append("_comm_shardings")
        for attr in attrs:
            if hasattr(other, attr) and getattr(other, attr) is not None:
                setattr(self, attr, getattr(other, attr))

    def _build(self, trainable, frozen, bufs, batch):
        model = self._model
        loss_fn = self._loss_fn
        opt = self._opt
        t_items = list(trainable.items())
        f_items = list(frozen.items())
        b_items = list(bufs.items())
        if self._opt_state is None:  # not adopted from a sibling
            self._init_opt_state(t_items)
            if self._restored_opt is not None:
                # elastic-checkpoint preload: replace the fresh zeros
                # (already placed per slot sharding) with the
                # snapshot's host arrays on the same shardings
                self._apply_restored_state()

        import contextlib

        if self._amp_level == "O1":
            from .. import amp as _amp_mod

            def _amp_ctx():
                return _amp_mod.auto_cast(
                    enable=True, level="O1", dtype=self._amp_dtype,
                    custom_white_list=self._amp_white,
                    custom_black_list=self._amp_black)
        else:
            _amp_ctx = contextlib.nullcontext

        def loss_of(pvals, fvals, bvals, avals, rngc):
            with engine.trace_mode(), _amp_ctx():
                prev_key = _random.push_traced_key(
                    jax.random.fold_in(_random._rng.base, rngc))
                saved = []
                try:
                    for (k, p) in t_items:
                        saved.append((p, p._value))
                        p._value = pvals[k]
                    for (k, p) in f_items:
                        saved.append((p, p._value))
                        p._value = fvals[k]
                    for (k, b) in b_items:
                        saved.append((b, b._value))
                        b._value = bvals[k]
                    scope = _jstate.push_buffer_scope()
                    args = [Tensor(a, stop_gradient=True, _internal=True)
                            if isinstance(a, jax.Array) or isinstance(
                                a, jnp.ndarray) else a for a in avals]
                    if loss_fn is not None:
                        out = model(*args[:-1])
                        loss = loss_fn(out, args[-1])
                    else:
                        loss = model(*args)
                    _jstate.pop_buffer_scope()
                    id2key = {id(b): k for k, b in b_items}
                    new_bvals = dict(bvals)
                    for buf, nv in scope:
                        kk = id2key.get(id(buf))
                        if kk is not None:
                            new_bvals[kk] = nv._value
                    lv = loss._value if isinstance(loss, Tensor) else loss
                    return lv.astype(jnp.float32), new_bvals
                finally:
                    for obj, v in saved:
                        obj._value = v
                    _random.pop_traced_key(prev_key)

        k_merge = self._accum_steps
        k_dispatch = self._steps_per_dispatch
        guard = self._guard_nonfinite
        # PTA093 build audit (raises under PADDLE_SANITIZE=numerics,
        # reports under PADDLE_ANALYSIS=1, silent disarmed): fp16
        # trainable params without a GradScaler or master weights
        from ..analysis.precision import audit_train_precision

        audit_train_precision(
            {k: str(p._value.dtype) for k, p in t_items},
            self._grad_scaler,
            getattr(opt, "_multi_precision", False),
            where=f"train_step:{type(model).__name__}")
        # numerics probe: armed AT BUILD fuses the per-tensor stats
        # reduction into the step; disarmed leaves nstats an empty
        # pytree — zero extra outputs, the lowering is bit-identical
        probe = _sanitize._numerics
        self._numerics_built = probe
        if probe:
            from ..monitor import numerics as _numerics_mod

        def one_step(pvals, opt_state, accum, comm, fvals, bvals,
                     avals, lr, rngc, scale):
            loss, new_bvals, grads, new_comm = self._grads_and_loss(
                loss_of, pvals, fvals, bvals, avals, rngc, scale,
                comm)
            # fused stats over loss/grads/params (pre-update: the
            # values THIS step consumed) — tiny packed reductions,
            # host-read every sample=N'th dispatch by _run_compiled
            nstats = (_numerics_mod.stats_tree(
                {"loss": loss, "grad": grads, "param": pvals})
                if probe else {})

            if guard:
                # fused all-finite predicate over loss + every grad
                # (check_finite_and_unscale)
                ok = jnp.isfinite(loss)
                for g in tree_util.tree_leaves(grads):
                    ok = jnp.logical_and(ok,
                                         jnp.all(jnp.isfinite(g)))
                if k_merge > 1:
                    # under gradient merge a whole-step cond
                    # passthrough would also skip the BOUNDARY zeroing
                    # — a trip on the k-th microstep would roll the
                    # window's grads into the next one and silently
                    # double-weight it. Instead the tripped microstep
                    # contributes ZERO gradient (and keeps its old
                    # buffers) while the accumulate/apply/zero cadence
                    # runs on schedule — the reference's
                    # check_finite_and_unscale zeroing semantics.
                    grads = {n: jnp.where(ok, g, jnp.zeros_like(g))
                             for n, g in grads.items()}
                    new_bvals = {k: jnp.where(ok, v, bvals[k])
                                 for k, v in new_bvals.items()}
                # a tripped step must not keep a residual computed
                # from non-finite gradients (quantizing inf poisons
                # the error buffer forever) — pass the old one
                # through, mirroring the opt-state passthrough
                new_comm = tree_util.tree_map(
                    lambda nc, oc: jnp.where(ok, nc, oc), new_comm,
                    comm)

            def _apply_all(_):
                if k_merge <= 1:
                    new_p, new_s = opt.apply_gradients(pvals, grads,
                                                       opt_state, lr)
                    return new_p, new_s, accum, new_bvals
                # gradient merge: accumulate; apply every k-th call
                acc = {n: accum[n] + grads[n].astype(jnp.float32)
                       for n in grads}

                def _apply(_):
                    merged = {n: (acc[n] / k_merge).astype(
                        grads[n].dtype) for n in acc}
                    new_p, new_s = opt.apply_gradients(pvals, merged,
                                                       opt_state, lr)
                    zeros = {n: jnp.zeros_like(acc[n]) for n in acc}
                    return new_p, new_s, zeros

                def _skip(_):
                    return pvals, opt_state, acc

                do_apply = (rngc % np.uint32(k_merge)) \
                    == np.uint32(k_merge - 1)
                new_p, new_s, new_acc = jax.lax.cond(do_apply, _apply,
                                                     _skip, None)
                return new_p, new_s, new_acc, new_bvals

            if guard and k_merge <= 1:
                # no merge window: a trip skips the update AND the
                # buffer commits — bit-identical to never having run
                # the batch; only the (non-finite) loss escapes as
                # evidence
                def _passthrough(_):
                    return pvals, opt_state, accum, bvals

                new_p, new_s, new_acc, new_b = jax.lax.cond(
                    ok, _apply_all, _passthrough, None)
                skip = (~ok).astype(jnp.uint32)
            else:
                new_p, new_s, new_acc, new_b = _apply_all(None)
                skip = ((~ok).astype(jnp.uint32) if guard
                        else jnp.uint32(0))
            return (new_p, new_s, new_acc, new_comm, new_b, loss,
                    skip, nstats)

        if k_dispatch <= 1:
            step_fn = one_step
        else:
            # fused multi-step dispatch: scan the SAME one_step body
            # over K stacked microbatches, carrying the donated
            # (params, opt_state, accum, comm residuals, buffers)
            # entirely on device. frozen params, lr and the loss
            # scale broadcast (closure); rng counters advance per
            # microstep so random streams match K sequential
            # dispatches bit-for-bit.
            def step_fn(pvals, opt_state, accum, comm, fvals, bvals,
                        avals, lr, rngc, scale):
                def body(carry, xs):
                    p, s, acc, cm, bv = carry
                    av, rc = xs
                    p, s, acc, cm, bv, loss, skip, ns = one_step(
                        p, s, acc, cm, fvals, bv, av, lr, rc, scale)
                    return (p, s, acc, cm, bv), (loss, skip, ns)

                rcs = rngc + jnp.arange(k_dispatch, dtype=jnp.uint32)
                ((p, s, acc, cm, bv),
                 (losses, skips, nstats)) = jax.lax.scan(
                    body, (pvals, opt_state, accum, comm, bvals),
                    (avals, rcs))
                return p, s, acc, cm, bv, losses, skips, nstats

        self._program = self._jit_step(step_fn, trainable, frozen, bufs,
                                       batch)

    def _grads_and_loss(self, loss_of, pvals, fvals, bvals, avals,
                        rngc, scale, comm):
        """One microstep's loss + gradients: value_and_grad over the
        traced forward, with dynamic loss scaling unscaled here (the
        gradients this returns are ALWAYS in unscaled units — the
        compressed override quantizes them, and quantizing scaled
        grads would waste code range on the scale factor). Returns
        (loss, new_bvals, grads, new_comm); the base path has no comm
        state to advance. Overridden by DistributedTrainStepCompiler
        when comm compression restructures the reduction."""
        if self._grad_scaler is not None:
            # dynamic loss scaling (check_finite_and_unscale +
            # update_loss_scaling, fused): backward runs on the
            # SCALED loss, gradients unscale before guard/apply,
            # the user-visible loss stays unscaled (aux)
            def scaled_loss_of(pv, fv, bv, av, rc):
                loss, nb = loss_of(pv, fv, bv, av, rc)
                return loss * scale, (loss, nb)

            (_, (loss, new_bvals)), grads = jax.value_and_grad(
                scaled_loss_of, has_aux=True)(pvals, fvals, bvals,
                                              avals, rngc)
            inv = (np.float32(1.0) / scale)
            grads = {n: (g.astype(jnp.float32) * inv).astype(
                g.dtype) for n, g in grads.items()}
        else:
            (loss, new_bvals), grads = jax.value_and_grad(
                loss_of, has_aux=True)(pvals, fvals, bvals, avals,
                                       rngc)
        return loss, new_bvals, grads, comm
