"""PTA04x donation sanitizer — static passes.

Buffer donation (`donate_argnums` / `input_output_aliases`) is the
TPU performance contract that keeps a fused train step in-place, and
the single largest source of review-caught bugs in this repo: host
references into donated buffers (`np.asarray` zero-copy snapshot
views), stale donated arrays fed back into a later dispatch, and
hand-built alias maps that only fail inside XLA. This module is the
STATIC half of the donation family:

  * `audit_donation(fn, args, donate_argnums)` — jaxpr-level audit of
    one donating callable: out-of-range donations, donated args that
    are returned unmodified (the caller's retained reference and the
    return value alias one freed buffer), donated args ALSO captured
    as closure constants, and donated args the program never consumes
    (wasted donation).                                       (PTA040)
  * `audit_aliases(...)` — `input_output_aliases` validity for the
    Pallas packers: shape/dtype equality per aliased pair, no output
    aliased twice, indices in range.                         (PTA042)
  * `lint_donation_source(...)` — AST pass (the CLI `--sanitize`
    donation leg): a name passed positionally to a call that donates
    it (literal `donate_argnums=`) and then read again later in the
    same function is a source-level use-after-donate.        (PTA040)

The runtime half (`PADDLE_SANITIZE=donation`: dispatch-site registry,
deleted-buffer checks, `owndata` snapshot verification) lives in
`paddle_tpu.monitor.sanitize` and reports PTA041/PTA043.
"""
from __future__ import annotations

import ast

import jax
from jax import tree_util
from jax.extend import core as jex_core

from ..core.tensor import Tensor
from .diagnostics import Report, Severity
from .jaxpr import fn_anchor
from .preflight import _walk_no_nested_defs

__all__ = ["audit_donation", "audit_aliases", "lint_donation_source"]


def _leaf_vals(arg):
    """Array leaves of one positional argument (Tensor-aware)."""
    leaves = tree_util.tree_leaves(
        arg, is_leaf=lambda x: isinstance(x, Tensor))
    return [v._value if isinstance(v, Tensor) else v for v in leaves]


def audit_donation(fn, args, donate_argnums, report=None, where=""):
    """Trace `fn(*args)` with `jax.make_jaxpr` and audit the donation
    contract of `donate_argnums` (positional indices into `args`,
    pytrees allowed). Purely static — nothing compiles or runs."""
    report = report if report is not None else Report()
    file, line = fn_anchor(fn)
    name = where or getattr(fn, "__name__", "fn")
    donate = ((donate_argnums,) if isinstance(donate_argnums, int)
              else tuple(donate_argnums))
    vals = [_leaf_vals(a) for a in args]
    for d in donate:
        if d < 0 or d >= len(args):
            report.add(
                "PTA040",
                f"{name}: donate_argnums={d} is out of range for "
                f"{len(args)} argument(s) — nothing is donated",
                file=file, line=line, severity=Severity.ERROR,
                analyzer="donation")
    donate = tuple(d for d in donate if 0 <= d < len(args))
    traced_args = [tree_util.tree_map(
        lambda v: v._value if isinstance(v, Tensor) else v, a,
        is_leaf=lambda x: isinstance(x, Tensor)) for a in args]
    try:
        closed = jax.make_jaxpr(fn)(*traced_args)
    except Exception as e:
        report.add(
            "PTA040",
            f"{name}: donation audit could not trace the function "
            f"({type(e).__name__}: {e})",
            file=file, line=line, severity=Severity.WARNING,
            analyzer="donation")
        return report
    jaxpr = closed.jaxpr
    # map each donated argnum to its flat invar slice
    counts = [len(vs) for vs in vals]
    offsets = [sum(counts[:i]) for i in range(len(counts))]
    invars = jaxpr.invars
    outvars = set(v for v in jaxpr.outvars
                  if not isinstance(v, jex_core.Literal))
    used = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, jex_core.Literal):
                used.add(v)
    for d in donate:
        for j in range(counts[d]):
            idx = offsets[d] + j
            if idx >= len(invars):
                continue
            v = invars[idx]
            leafdesc = (f"argument {d}" if counts[d] == 1
                        else f"argument {d} (leaf {j})")
            if v in outvars:
                report.add(
                    "PTA040",
                    f"{name}: donated {leafdesc} is returned "
                    "UNMODIFIED — the caller's retained reference "
                    "and the returned value alias one buffer the "
                    "donation frees/reuses; drop the donation or "
                    "stop returning the input",
                    file=file, line=line, analyzer="donation")
            elif v not in used:
                report.add(
                    "PTA040",
                    f"{name}: donated {leafdesc} is never consumed "
                    "by the traced program — the donation frees a "
                    "buffer for nothing (likely a stale argnum)",
                    file=file, line=line, analyzer="donation")
    # donated arrays also captured as closure constants: the SECOND
    # call reads a const buffer the FIRST call's donation deleted
    donated_leaves = [v for d in donate for v in vals[d]]
    for c in closed.consts:
        for v in donated_leaves:
            if c is v:
                report.add(
                    "PTA040",
                    f"{name}: a donated argument is ALSO captured as "
                    "a closure constant — after the first dispatch "
                    "donates it, every later call reads a deleted "
                    "buffer; pass it as an argument only",
                    file=file, line=line, severity=Severity.ERROR,
                    analyzer="donation")
    return report


def audit_aliases(aliases, in_shapes, out_shapes, in_dtypes=None,
                  out_dtypes=None, report=None, where=""):
    """Validate an `input_output_aliases` map ({input_idx:
    output_idx}) against operand/result shapes (+ dtypes when given):
    each pair must match exactly, each output aliased at most once,
    indices in range. The Pallas packers call this before launching
    so a bad hand-built map fails as PTA042 with names instead of an
    XLA layout error."""
    report = report if report is not None else Report()
    name = where or "pallas_call"
    seen_out = {}
    for i, o in dict(aliases).items():
        if i < 0 or i >= len(in_shapes):
            report.add("PTA042",
                       f"{name}: alias input index {i} out of range "
                       f"for {len(in_shapes)} operand(s)",
                       analyzer="donation")
            continue
        if o < 0 or o >= len(out_shapes):
            report.add("PTA042",
                       f"{name}: alias output index {o} out of range "
                       f"for {len(out_shapes)} result(s)",
                       analyzer="donation")
            continue
        if o in seen_out:
            report.add("PTA042",
                       f"{name}: output {o} aliased twice (inputs "
                       f"{seen_out[o]} and {i}) — one buffer cannot "
                       "back two donations",
                       analyzer="donation")
        seen_out[o] = i
        if tuple(in_shapes[i]) != tuple(out_shapes[o]):
            report.add("PTA042",
                       f"{name}: alias {i}->{o} shape mismatch "
                       f"{tuple(in_shapes[i])} vs "
                       f"{tuple(out_shapes[o])} — the donated buffer "
                       "cannot be reused in place",
                       analyzer="donation")
        elif (in_dtypes is not None and out_dtypes is not None
                and str(in_dtypes[i]) != str(out_dtypes[o])):
            report.add("PTA042",
                       f"{name}: alias {i}->{o} dtype mismatch "
                       f"{in_dtypes[i]} vs {out_dtypes[o]}",
                       analyzer="donation")
    return report


# ---------------------------------------------------------------------------
# AST pass (CLI --sanitize donation)
# ---------------------------------------------------------------------------

def _literal_argnums(kw):
    """donate_argnums literal -> tuple of ints, or None when the
    value is computed (nothing to check statically)."""
    v = kw.value
    if isinstance(v, ast.Constant) and isinstance(v.value, int):
        return (v.value,)
    if isinstance(v, (ast.Tuple, ast.List)):
        out = []
        for e in v.elts:
            if not (isinstance(e, ast.Constant)
                    and isinstance(e.value, int)):
                return None
            out.append(e.value)
        return tuple(out)
    return None


def _donating_calls(stmt):
    """(call, argnums) pairs inside one statement: direct
    `jit(fn, donate_argnums=...)(x, y)` invocations (the donated args
    are the OUTER call's) and jitted-callable constructions whose
    later calls the caller tracks by name."""
    makers = []
    for n in _walk_no_nested_defs(stmt):
        if not isinstance(n, ast.Call):
            continue
        kw = next((k for k in n.keywords
                   if k.arg == "donate_argnums"), None)
        if kw is None:
            continue
        nums = _literal_argnums(kw)
        if nums is None:
            continue
        makers.append((n, nums))
    direct = []
    for n in _walk_no_nested_defs(stmt):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Call):
            for maker, nums in makers:
                if n.func is maker:
                    direct.append((n, nums))
    # an assignment `jfn = jax.jit(fn, donate_argnums=...)` publishes
    # the donation to every later `jfn(...)` call in the same scope
    named = {}
    if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
            and isinstance(stmt.targets[0], ast.Name):
        for maker, nums in makers:
            if stmt.value is maker:
                named[stmt.targets[0].id] = nums
    return direct, named


def _donated_names(call, argnums):
    """Plain-Name positional args at the donated indices."""
    out = {}
    for i in argnums:
        if i < len(call.args) and isinstance(call.args[i], ast.Name):
            out[call.args[i].id] = (i, call.lineno)
    return out


def _assigned_names(stmt):
    out = set()
    for n in [stmt, *_walk_no_nested_defs(stmt)]:
        if isinstance(n, (ast.Assign,)):
            for t in n.targets:
                for nn in ast.walk(t):
                    if isinstance(nn, ast.Name):
                        out.add(nn.id)
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign)):
            for nn in ast.walk(n.target):
                if isinstance(nn, ast.Name):
                    out.add(nn.id)
    return out


def lint_donation_source(source, filename="<string>", report=None):
    """Source-level use-after-donate: within one function body, a
    Name passed at a donated position of a donating call and READ
    again in a later statement (without being rebound) aliases a
    freed buffer — the PR-8 stale-buffer shape, caught before any
    dispatch."""
    report = report if report is not None else Report()
    try:
        tree = ast.parse(source)
    except SyntaxError:
        return report  # preflight reports the parse error
    for fdef in ast.walk(tree):
        if not isinstance(fdef, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
            continue
        donated = {}   # name -> (argnum, donate lineno)
        jitted = {}    # callable name -> argnums
        for stmt in fdef.body:
            # reads of previously-donated names in THIS statement
            # (before this statement's own donations register)
            reads = [n for n in _walk_no_nested_defs(stmt)
                     if isinstance(n, ast.Name)
                     and isinstance(n.ctx, ast.Load)]
            for n in reads:
                if n.id in donated:
                    argnum, dline = donated[n.id]
                    report.add(
                        "PTA040",
                        f"'{n.id}' was donated (argnum {argnum}) at "
                        f"line {dline} and is used again — its "
                        "buffer is freed/reused by the donating "
                        "program; use the returned value instead",
                        file=filename, line=n.lineno,
                        analyzer="donation")
                    del donated[n.id]  # one report per donation
            # new donations from this statement
            direct, named = _donating_calls(stmt)
            jitted.update(named)
            for call, nums in direct:
                donated.update(_donated_names(call, nums))
            # calls of tracked jitted names donate their args too
            for n in _walk_no_nested_defs(stmt):
                if isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Name) \
                        and n.func.id in jitted:
                    donated.update(
                        _donated_names(n, jitted[n.func.id]))
            # rebinding clears the hazard — AFTER this statement's
            # donations register, so `x = jfn(x)` (donate then rebind
            # to the returned value) is recognized as safe
            for name in _assigned_names(stmt) & set(donated):
                del donated[name]
    return report
