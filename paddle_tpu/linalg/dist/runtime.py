"""Shared runtime for the distributed linear-algebra tier.

Everything dist algorithms need to ride the production spine lives
here, in one place:

- `Grid`: the 2D (row axis x col axis) process grid carved out of the
  live Fleet mesh (PADDLE_LINALG_AXES override), the SUMMA layout of
  arxiv 2112.09017 expressed as mesh axis names.
- PTA05x spec lints on every ShardedMatrix layout BEFORE compile
  (structural errors always raise; findings ride the analysis
  Finding/Report counters when PADDLE_ANALYSIS/PADDLE_SANITIZE arms
  them).
- the program cache + compile path: programs lower through jax.jit
  like every other subsystem (JAX's persistent compilation cache
  serves them where it is armed), with `linalg_compile` flight
  spans.
- the dispatch path: `linalg_dispatch` chaos site, `linalg` flight
  in-flight spans (watchdog-visible), and the
  linalg/{matmuls,factorizations,eigensolves,bytes} counters.
- trace-level broadcast/psum/all_gather helpers that route through
  `distributed/collective.py` inside shard_map bodies, so the
  existing comm/<op>/{calls,bytes} telemetry prices the algorithm's
  collective traffic for free.
"""
from __future__ import annotations

import math
import os
import time as _time
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp

from ...core import monitor as _monitor
from ...core.tensor import Tensor
from ...distributed import mesh as _mesh_mod
from ...monitor import chaos as _chaos
from ...monitor import flight as _flight
from ...monitor import perf as _perf
from ...monitor import sanitize as _sanitize

__all__ = ["Grid", "grid", "lint_spec", "compile_program", "dispatch",
           "bcast", "psum", "gather", "axes_group",
           "clear_program_cache"]

# compiled dist programs, (label, mesh sig, arg sig) -> executable —
# same LRU discipline as cost_model (the executables pin device memory
# for their constants, so a sweep over many shapes must not grow this
# without bound)
_PROGRAMS_MAX = 32
_programs: OrderedDict = OrderedDict()

# one Group per axis tuple: collective.py groups are cheap but
# registered forever in mesh._groups, so per-compile creation would
# leak registry entries across a planner sweep
_axis_groups: dict = {}


def axes_group(axes):
    """The collective Group for a tuple of mesh axis names."""
    axes = tuple(axes)
    g = _axis_groups.get(axes)
    if g is None:
        g = _mesh_mod.new_group_for_axes(axes)
        _axis_groups[axes] = g
    return g


class Grid:
    """A 2D process grid (rows x cols) over the live mesh. `cx` may be
    None: a 1D grid (all parallelism on rows) — the tall-skinny /
    small-world degenerate SUMMA case."""

    def __init__(self, mesh, rx, cx):
        self.mesh = mesh
        self.rx = rx
        self.cx = cx

    @property
    def px(self):
        return int(self.mesh.shape[self.rx])

    @property
    def py(self):
        return int(self.mesh.shape[self.cx]) if self.cx else 1

    @property
    def nranks(self):
        return self.px * self.py

    def row_axes(self):
        """Axes a ROW of the grid spans (broadcast within a row goes
        along the COLUMN axis)."""
        return (self.cx,) if self.cx else ()

    def col_axes(self):
        return (self.rx,)

    def all_axes(self):
        return (self.rx, self.cx) if self.cx else (self.rx,)

    def block_spec(self):
        """P(rx, cx): the 2D block layout."""
        from jax.sharding import PartitionSpec as P

        return P(self.rx, self.cx) if self.cx else P(self.rx, None)

    def row_spec(self):
        """P((rx, cx), None): 1D block-row layout over the whole
        grid (tall-skinny TSQR layout)."""
        from jax.sharding import PartitionSpec as P

        return P(self.all_axes() if self.cx else self.rx, None)

    def sig(self):
        """Cache/digest signature: axis names + sizes + the device
        assignment (reshaped/reordered meshes must not collide in the
        persistent compile cache — the DistributedTrainStepCompiler
        contract)."""
        return (self.rx, self.cx,
                tuple(int(self.mesh.shape[a])
                      for a in self.mesh.axis_names),
                tuple(int(d.id) for d in self.mesh.devices.flat))

    def __repr__(self):
        return (f"Grid({self.px}x{self.py}, row_axis={self.rx!r}, "
                f"col_axis={self.cx!r})")


def grid(mesh=None, row_axis=None, col_axis=None):
    """Resolve the process grid from the live Fleet mesh.

    Default axis pick: PADDLE_LINALG_AXES='rx,cx' when set, else the
    first two mesh axes with size > 1 in mesh order (one -> 1D grid,
    none -> 1x1 on the first axis). Explicit row_axis/col_axis win."""
    mesh = mesh if mesh is not None else _mesh_mod.ensure_mesh()
    names = tuple(mesh.axis_names)
    env = os.environ.get("PADDLE_LINALG_AXES")
    if row_axis is None and col_axis is None and env:
        parts = [p.strip() for p in env.split(",") if p.strip()]
        row_axis = parts[0] if parts else None
        col_axis = parts[1] if len(parts) > 1 else None
    if row_axis is None:
        big = [a for a in names if int(mesh.shape[a]) > 1]
        row_axis = big[0] if big else names[0]
        if col_axis is None:
            col_axis = big[1] if len(big) > 1 else None
    for a in (row_axis, col_axis):
        if a is not None and a not in names:
            raise ValueError(
                f"paddle.linalg.dist: grid axis {a!r} is not a mesh "
                f"axis (mesh axes: {list(names)}) — set "
                "PADDLE_LINALG_AXES or pass row_axis/col_axis")
    if col_axis == row_axis:
        raise ValueError(
            "paddle.linalg.dist: row_axis and col_axis must be "
            f"distinct mesh axes (both {row_axis!r})")
    return Grid(mesh, row_axis, col_axis)


def lint_spec(spec, shape, mesh, *, name="matrix", where="linalg.dist"):
    """PTA05x sharding lints on a ShardedMatrix spec BEFORE compile.

    Structural errors (unknown axis PTA050, indivisible dim PTA051,
    rank mismatch PTA052) always raise — the dist algorithms cannot
    run on them and shard_map would only fail later and worse. The
    findings additionally ride the analysis/<code>/findings counters
    when PADDLE_ANALYSIS=1 or PADDLE_SANITIZE=sharding is armed (and
    ONLY then: the disarmed path must leave zero counters — the
    bench.py provenance contract)."""
    from ...analysis.sharding import check_spec

    mesh_axes = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    report = check_spec(spec, shape, mesh_axes, name=name, where=where)
    if report.findings:
        armed = False
        try:
            from ...analysis import enabled as _analysis_enabled

            armed = _sanitize._sharding or _analysis_enabled()
        except Exception:
            pass
        if armed:
            report.record()
        if report.errors:
            raise ValueError(
                "paddle.linalg.dist: PTA05x sharding lint failed for "
                f"{name}:\n"
                + "\n".join(f.format() for f in report.errors))
    return report


def _arg_sig(args):
    return tuple((tuple(int(d) for d in np.shape(a)),
                  str(getattr(a, "dtype", np.asarray(a).dtype)))
                 for a in args)


def shard_map(body, mesh, in_specs, out_specs):
    """The shard_map island builder shared with ring attention
    (distributed.mesh.shard_map_compat)."""
    return _mesh_mod.shard_map_compat(body, mesh, in_specs,
                                      out_specs)


def compile_program(label, build, grid_, args, extra_key=()):
    """Compiled executable for a dist program.

    `build()` returns the traceable global-array function (usually a
    shard_map island). Keyed by (label, grid signature, arg
    shapes/dtypes, extra_key); fresh compiles lower through jax.jit,
    and JAX's persistent compilation cache (jit.persistent_cache)
    serves them where it is armed."""
    key = (label, grid_.sig(), _arg_sig(args), tuple(extra_key))
    ent = _programs.get(key)
    if ent is not None:
        _programs.move_to_end(key)
        _monitor.stat_add("linalg/program_cache/hits", 1)
        return ent
    t0 = _time.perf_counter()
    tok = _flight.begin("linalg_compile", label) \
        if _flight.recorder.enabled else None
    try:
        compiled = jax.jit(build()).lower(*args).compile()
    finally:
        _flight.end(tok)
    _monitor.stat_add("linalg/compiles", 1)
    _monitor.stat_add("linalg/compile_us",
                      int((_time.perf_counter() - t0) * 1e6))
    # roofline ledger: the compiled executable is already in hand on
    # this fresh-compile path, so the cost capture is free (no extra
    # backend compile, unlike the jit/serving capture sites)
    _perf.record_program_cost(f"linalg:{label}", compiled)
    _programs[key] = compiled
    while len(_programs) > _PROGRAMS_MAX:
        _programs.popitem(last=False)
    return compiled


def clear_program_cache():
    """Drop every cached dist executable (tests; mesh teardown)."""
    _programs.clear()


def _nbytes(arrs):
    n = 0
    for a in arrs:
        try:
            n += int(np.prod(np.shape(a))) * jnp.dtype(a.dtype).itemsize
        except Exception:
            pass
    return n


def dispatch(kind, label, compiled, args):
    """Run one compiled dist program through the production spine:
    `linalg_dispatch` chaos site, a watchdog-visible `linalg`
    in-flight flight span, and the linalg/{<kind>,bytes} counters
    (`kind` in matmuls/factorizations/eigensolves)."""
    nbytes = _nbytes(args)
    if _chaos._armed:
        _chaos.hit("linalg_dispatch", op=label)
    tok = _flight.begin("linalg", label, bytes=nbytes) \
        if _flight.recorder.enabled else None
    timing = _perf.dispatch_timing_enabled()
    t0 = _time.perf_counter() if timing else None
    try:
        out = compiled(*args)
        if timing:
            # block before the span closes so the flight `linalg`
            # span and the dispatch histogram both see device time
            jax.block_until_ready(out)
            _perf.observe_dispatch(
                f"linalg:{label}",
                int((_time.perf_counter() - t0) * 1e6))
    finally:
        _flight.end(tok)
    _monitor.stat_add(f"linalg/{kind}", 1)
    _monitor.stat_add("linalg/bytes",
                      nbytes + _nbytes(jax.tree_util.tree_leaves(out)))
    return out


# ---------------------------------------------------------------------------
# trace-level collectives: the distributed/collective.py surface, made
# convenient for shard_map bodies on raw per-shard arrays. Each helper
# wraps the shard in a Tensor and calls the instrumented module
# function, so comm/<op>/{calls,bytes} counters + flight events record
# the algorithm's analytic traffic at trace time (the established
# convention: bytes are the static per-rank payload).
# ---------------------------------------------------------------------------

def bcast(val, axes, src):
    """Broadcast `val` from group-local flat rank `src` across mesh
    `axes` (masked-psum broadcast — collective.broadcast's traced
    path). Identity on an empty axis tuple (1D-grid degenerate)."""
    axes = tuple(a for a in axes if a is not None)
    if not axes:
        return val
    from ...distributed import collective as C

    t = Tensor(val, stop_gradient=True, _internal=True)
    C.broadcast(t, src=int(src), group=axes_group(axes))
    return t._value


def psum(val, axes):
    """Sum-reduce `val` across mesh `axes` (collective.all_reduce's
    traced path)."""
    axes = tuple(a for a in axes if a is not None)
    if not axes:
        return val
    from ...distributed import collective as C

    t = Tensor(val, stop_gradient=True, _internal=True)
    C.all_reduce(t, group=axes_group(axes))
    return t._value


def gather(val, axes):
    """all_gather across mesh `axes`, stacked on a new leading dim of
    length prod(axis sizes), ordered row-major by axis order (== the
    group-local flat rank)."""
    axes = tuple(a for a in axes if a is not None)
    if not axes:
        return val[None]
    from ...distributed import collective as C

    parts = []
    C.all_gather(parts, Tensor(val, stop_gradient=True,
                               _internal=True),
                 group=axes_group(axes))
    return jnp.stack([p._value for p in parts], axis=0)


def flat_rank(grid_):
    """This shard's group-local flat rank on the grid, row-major —
    matches gather()'s leading-dim order and bcast()'s src index."""
    from jax import lax

    r = lax.axis_index(grid_.rx)
    if grid_.cx:
        r = r * grid_.py + lax.axis_index(grid_.cx)
    return r


def block_divisor(n, *counts):
    """Largest candidate block: gcd of the per-axis local extents."""
    g = 0
    for c in counts:
        g = math.gcd(g, n // c)
    return g
