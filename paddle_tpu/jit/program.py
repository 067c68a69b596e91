"""jit.Program — a named jitted function of the package.

The one place that decides how a program is compiled, cached, named
and measured. `StaticFunction`, `TrainStepCompiler` (and the
distributed one) and `LLMEngine` each hold Programs; what used to be
copied into each of them lives here:

    with program.dispatch():              # count; first: arm, span
        out = program.bind(*args)()       # the jax.jit call itself
    program.capture()                     # first: footprint, cost,
                                          # a full collection

  * first dispatch or not: a Program is one `jax.jit` at one argument
    signature (a caller with several — `to_static`'s cache keys, the
    engine's prefill widths — keeps one Program each, named by
    `specialised`), so its first call is the one that compiles. A
    later call that grows the jit's trace cache compiled too (a new
    batch shape, weak types that strengthened): `compiled()` finds it
    after the fact, from `cache_size()`, at no walk over the
    arguments;
  * the counters `jit/<family>/cache_{miss,hit}`, `/compile_us`,
    `/mem_capture_us` and the histogram `jit/hist/compile_us`;
  * the span `compile/<family>` (id `program`) around a first
    dispatch, through flight.begin/end so that the watchdog sees a
    stuck compile, closed on a raise; `retrace=1`, in the ring alone,
    for a later call that compiled;
  * `arm_compile_cache()` before that compile: JAX's persistent
    compilation cache (jit.persistent_cache) is what a second process
    starts warm from;
  * the capture, `capture()`: after the first successful call, under
    `compile/capture/<name>`, one `lower(...).compile()` over the
    avals that call itself used (recorded before it ran, so a donated
    buffer is never touched; the same lowering, so the compiled
    object is the one the call made and costs no second backend
    compile), then `mem/program/<name>/*` and `perf/program/<name>/*`
    from that one object. PADDLE_MEM_PROGRAM and PADDLE_PERF_PROGRAM
    are read here and nowhere else; a failed capture never fails the
    caller. Then, asked for or not, `collect_after_compile()`: the
    garbage of a trace and two lowerings is collected where nothing
    waits, not in the middle of a later step.

Why `bind(*args)()` and not `program(*args)`: the call has to reach
`jax.jit` from the caller's own frame. With two frames of a
`__call__` between them, the MLIR lowering of every program of the
engine took 0.2-0.5 s longer on the chip (470 against 255 ms a GPT-2
prefill bucket, the function, the arguments and the cache being the
same; PERF.md section 6, PR 29): `bind` returns a `functools.partial`
of the jitted function, which is called from C. The function is
handed to `jax.jit` as it is, too: what XLA and a profiler trace call
the program does not change with the name given here. Dispatch
timing (PADDLE_PERF_DISPATCH) stays with the callers, who know what
to block on; they ask `compiled()` to leave a compiling sample out.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
from jax import tree_util

from ..core import monitor as _monitor
from ..monitor import flight as _flight
from ..monitor import memory as _memory
from ..monitor import perf as _perf
from . import persistent_cache

__all__ = ["Program", "arm_compile_cache", "specialised"]


def arm_compile_cache():
    """Arm JAX's persistent compilation cache (persistent_cache.
    arm_native) before a compile on an accelerator backend. CPU runs
    — the test suite — are left alone: their thousands of tiny
    programs are not worth persisting, and an entry point that wants
    the cache on CPU arms it itself."""
    if jax.default_backend() != "cpu":
        persistent_cache.arm_native()


def collect_after_compile():
    """A full collection now, after a first dispatch on an
    accelerator. Tracing and lowering a program leave behind tens of
    thousands of dead tracers, equations and MLIR wrappers, many of
    them old enough by then to sit in the collector's last
    generation: left there they are what makes it run its full pass
    (30-100 ms over a heap with JAX in it) a few hundred steps later,
    inside a decode or a train step. Here nothing waits: the compile
    took seconds. CPU runs are left alone, as `arm_compile_cache`
    leaves them: a test suite's thousands of tiny programs would pay
    50 ms each."""
    if jax.default_backend() != "cpu":
        gc.collect()


def specialised(name, n):
    """The name of the n-th shape specialisation of one function, in
    the order first run: the first keeps the plain name, later ones
    `#n` (gauges of a tail-batch entry must not overwrite the
    full-batch one's)."""
    return name if n == 0 else f"{name}#{n}"


def _aval(x):
    """What a lowering needs of one argument leaf, without its
    buffer: shape, dtype, weak type and, where the array is committed
    to one, its sharding — as the call itself resolves them."""
    if isinstance(x, jax.core.Tracer):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    weak_type=x.aval.weak_type)
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.aval.weak_type,
            sharding=x.sharding if x.committed else None)
    return x


class _Dispatch:
    """`with program.dispatch():` — one dispatch's count and, where it
    is the first, its compile span."""

    __slots__ = ("_program", "_opened")

    def __init__(self, program):
        self._program = program

    def __enter__(self):
        """Count the dispatch; before the first, arm the cache and
        open `compile/<family>`."""
        prog = self._program
        if prog._ran:
            _monitor.stat_add(prog._hit, 1)
            self._opened = None
            return
        prog._called = False
        _monitor.stat_add(prog._miss, 1)
        _flight.record("jit_cache_miss", fn=prog.family)
        arm_compile_cache()
        self._opened = (
            _flight.begin("compile", prog.family, program=prog.name),
            time.perf_counter())

    def __exit__(self, exc_type, exc, tb):
        if self._opened is None:
            return
        prog = self._program
        token, t0 = self._opened
        _flight.end(token)
        compile_us = int((time.perf_counter() - t0) * 1e6)
        _monitor.stat_add(f"jit/{prog.family}/compile_us", compile_us)
        # ONE compile-time distribution across every program: the
        # per-family counters fan out too wide to read a fleet p99 from
        _monitor.hist_observe("jit/hist/compile_us", compile_us)
        if exc_type is None and prog._called:
            prog._ran = True
        else:
            prog._pending = None


class Program:
    """`fn` jitted (with `donate_argnums` and whatever else `jax.jit`
    takes), named `name`. `family` is the name its counters and its
    compile span share with the other specialisations of one function
    (`to_static`'s entries, the train step's K); by default its own."""

    def __init__(self, fn, name, donate_argnums=(), family=None,
                 **jit_kw):
        self._jit = jax.jit(fn, donate_argnums=donate_argnums, **jit_kw)
        self.name = name
        self.family = family = family or name
        self._hit = f"jit/{family}/cache_hit"
        self._miss = f"jit/{family}/cache_miss"
        self._ran = False       # a first call has succeeded
        self._called = False    # bind() since the dispatch began
        # what compiled() reads: None, nothing bound since it was
        # asked; () the first call; (trace cache size, time) a later
        self._bound = None
        self._pending = None    # (avals or None, want_mem, want_cost)
        self.memory = None      # memory_analysis() byte dict
        self.cost = None        # cost_analysis() flop/byte dict

    def dispatch(self):
        """A context around one dispatch: counts it and, where it is
        the first, arms the cache and holds `compile/<family>` open
        over whatever of the caller's the block takes in (the train
        step's prepare and finish, the engine's transfer). The calls
        inside it are `bind(*args)()`; several count as one."""
        return _Dispatch(self)

    def bind(self, *args):
        """The jitted function over `args`, to be called at once and
        with nothing: `program.bind(*args)()`, inside `dispatch()`."""
        self._called = True
        if self._ran:
            self._bound = (self.cache_size(), time.perf_counter())
        else:
            self._bound = ()
            want_mem = _memory.program_capture_enabled()
            want_cost = _perf.program_capture_enabled()
            self._pending = (
                tree_util.tree_map(_aval, args)
                if want_mem or want_cost else None,
                want_mem, want_cost)
        return functools.partial(self._jit, *args)

    def compiled(self):
        """Did the call just made compile: the first, or one that
        grew the jit's trace cache? Asked right after it, by a caller
        that times its dispatches: the retrace's `compile/<family>`
        (`retrace=1`, ring only) lands under the span open now."""
        bound, self._bound = self._bound, None
        if not bound:
            return bound is not None
        n0, t0 = bound
        if n0 is None or self.cache_size() == n0:
            return False
        _flight.closed_span(f"compile/{self.family}", t0,
                            time.perf_counter(), program=self.name,
                            retrace=1)
        return True

    def capture(self):
        """The footprint and cost of the program a first call just
        compiled, where asked for, then `collect_after_compile()`;
        nothing after any other call. The caller places it after
        that call (a raise never reaches it), outside whatever it
        times itself."""
        pending, self._pending = self._pending, None
        if pending is None:
            return
        avals, want_mem, want_cost = pending
        if avals is not None:
            self._capture(avals, want_mem, want_cost)
        collect_after_compile()

    def _capture(self, avals, want_mem, want_cost):
        try:
            # the span lets the watchdog's in-flight table and
            # jit/<family>/mem_capture_us attribute the time
            t0 = time.perf_counter()
            with _flight.in_flight("capture", self.name,
                                   program=self.name):
                compiled = self._jit.lower(*avals).compile()
            _monitor.stat_add(
                f"jit/{self.family}/mem_capture_us",
                int((time.perf_counter() - t0) * 1e6))
            if want_mem:
                self.memory = _memory.record_program_memory(
                    self.name, compiled)
            if want_cost:
                self.cost = _perf.record_program_cost(
                    self.name, compiled)
        except Exception:
            pass  # footprints are observability, never a build error

    def lower(self, *args):
        return self._jit.lower(*args)

    def cache_size(self):
        """Entries in the jit's trace cache; None when jax stops
        exposing the probe (retraces then pass for dispatches)."""
        try:
            return self._jit._cache_size()
        except Exception:
            return None
