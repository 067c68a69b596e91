"""paddle_tpu.monitor.perf — compute/roofline observability: the
FLOPs-and-bytes axis of the telemetry stack (the memory module's
compute twin).

The stack could already tell you a request's latency (trace), a
rank's memory (memory) and a hang's stack (flight) — but not where a
step's FLOPs and bytes go: MFU was hand-computed in bench.py from
analytic formulas against a hard-coded v5e peak, and
`compiled.cost_analysis()` was consulted only by the auto-parallel
planner. This module closes that gap three ways:

  * per-program cost ledger — jit records each compiled program's
    `cost_analysis()` (flops, bytes accessed, transcendentals)
    through `record_program_cost()` at every fresh cache entry
    (`StaticFunction`, `TrainStepCompiler` and its distributed
    subclass, the serving decode/prefill programs, `linalg:<label>`
    programs); gauges land under `perf/program/<name>/...` and
    `jit.cache_report()` carries the same numbers ("cost" fields)
    into every flight dump bundle, exactly like the memory ledger.

  * measured attribution — the capture sites observe each program's
    dispatch wall time (blocked on result ready — async dispatch
    would otherwise time the enqueue, not the execution) into
    `jit/hist/<name>/dispatch_us` histograms. Ledger / measurement
    combine in `perf_report()` into achieved FLOP/s, arithmetic
    intensity and per-program MFU against the device-kind peak table
    below, with a roofline verdict per program — compute-bound,
    HBM-bound, or comm-bound (the comm leg priced from the
    `comm/<op>/wire_bytes` counters against the interconnect
    bandwidth). `python -m paddle_tpu.monitor perf` renders the
    table; StepTimer's `step/attrib/{device,host,io,comm}_us`
    decomposition reads the flight ring's spans per step.

  * regression trail — bench.py embeds the ledger + the
    analytic-vs-compiler FLOPs drift ratio as `extra.perf` in every
    record; `benchmarks/regress.py` gates the BENCH_r*.json trail.

Env knobs: PADDLE_PERF_PROGRAM (0 disables cost capture at jit build
— same gating discipline as PADDLE_MEM_PROGRAM; disarmed runs leave
perf/* at zero, the bench-provenance contract), PADDLE_PERF_DISPATCH
(0 disables dispatch wall-time histograms — each observation blocks
on the program's outputs, trading dispatch pipelining for measured
attribution), PADDLE_PERF_STEP (0 disables the StepTimer step-time
decomposition), PADDLE_PEAK_TFLOPS / PADDLE_HBM_GBPS /
PADDLE_ICI_GBPS (peak-table overrides for chips the table doesn't
know).
"""
from __future__ import annotations

from ..core import monitor as _cmon
from ..core.monitor import snapshot_quantile
from . import flight as _flight
from .flight import _env_float, _env_on  # shared env-parsing semantics

__all__ = [
    "program_capture_enabled", "dispatch_timing_enabled",
    "step_attrib_enabled", "extract_cost_analysis",
    "record_program_cost", "observe_dispatch", "program_costs",
    "device_peaks", "roofline_verdict", "perf_report",
    "PEAK_TABLE",
]


def program_capture_enabled():
    """PADDLE_PERF_PROGRAM gate for cost_analysis capture at jit
    build. Default on; the capture rides the SAME extra backend
    compile the memory footprint capture already pays (the compiled
    object is shared), so disabling memory capture alone does not
    save the compile unless this is off too."""
    return _env_on("PADDLE_PERF_PROGRAM", True)


def dispatch_timing_enabled():
    """PADDLE_PERF_DISPATCH gate for per-program dispatch wall-time
    histograms. Each observation blocks on the dispatch's outputs
    (the bench PR-12 discipline — jax dispatch is async and an
    unblocked timer measures the enqueue), which serializes the
    host/device overlap the latency-hiding pipeline buys; 0 restores
    fully async dispatch."""
    return _env_on("PADDLE_PERF_DISPATCH", True)


def step_attrib_enabled():
    """PADDLE_PERF_STEP gate for StepTimer's per-step
    `step/attrib/*` decomposition (a flight-ring tail walk per
    step)."""
    return _env_on("PADDLE_PERF_STEP", True)


# ---------------------------------------------------------------------------
# Per-program cost ledger (fed by the jit/serving/linalg build paths)
# ---------------------------------------------------------------------------

# (ledger key, cost_analysis() key) — XLA spells the byte counter
# with a space
_COST_FIELDS = (
    ("flops", "flops"),
    ("bytes_accessed", "bytes accessed"),
    ("transcendentals", "transcendentals"),
)


def extract_cost_analysis(compiled):
    """`compiled.cost_analysis()` as a plain dict (None when the
    backend exposes no analysis). Normalizes the cross-version shape:
    older jax returns a one-element list of per-computation dicts,
    newer returns the dict directly."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for key, src in _COST_FIELDS:
        try:
            v = float(ca.get(src, 0.0) or 0.0)
        except (TypeError, ValueError):
            v = 0.0
        # XLA reports -1 for "unknown" on some backends — a negative
        # FLOP count would poison every downstream ratio
        out[key] = int(v) if v > 0 else 0
    return out


def record_program_cost(name, compiled):
    """extract_cost_analysis() plus the `perf/program/<name>/...`
    gauge writes — what every capture site calls per fresh compiled
    program. Returns the cost dict (cache_report's "cost" field), or
    None when the backend has no analysis OR capture is disabled —
    callers gate on program_capture_enabled() before paying a
    compile, but this re-check keeps the zero-counter contract even
    for sites that get a compiled object for free."""
    if not program_capture_enabled():
        return None
    out = extract_cost_analysis(compiled)
    if out is None:
        return None
    for key, _ in _COST_FIELDS:
        _cmon.stat_set(f"perf/program/{name}/{key}", out[key])
    return out


def observe_dispatch(name, dur_us):
    """One blocked-on-ready dispatch wall-time observation for
    program `name` — the measured leg the roofline divides the
    ledger's FLOPs by."""
    _cmon.hist_observe(f"jit/hist/{name}/dispatch_us", dur_us)


def program_costs(report=None):
    """Per-program cost analyses off the live jit caches (the same
    numbers jit.cache_report() embeds as "cost") — {name: cost dict}.
    Pass a precomputed cache_report() list as `report` to skip the
    live-compiler walk (dump bundles hold one as jit_caches). The
    naming mirrors memory.program_footprints: kind:fn, "#i" ordinals
    for shape-specialized to_static entries, "(n)" suffixes for
    sibling compilers sharing kind:fn."""
    if report is None:
        try:
            from .. import jit as _jit

            report = _jit.cache_report()
        except Exception:
            return {}
    out = {}

    def _put(name, c):
        key, n = name, 2
        while key in out:
            key = f"{name}({n})"
            n += 1
        out[key] = c

    for ent in report:
        cost = ent.get("cost")
        if not cost:
            continue
        name = f"{ent.get('kind')}:{ent.get('fn')}"
        if isinstance(cost, list):
            for i, c in enumerate(cost):
                if c:
                    _put(name if i == 0 else f"{name}#{i}", c)
        else:
            _put(name, cost)
    return out


# ---------------------------------------------------------------------------
# Device-kind peak table + roofline math
# ---------------------------------------------------------------------------

# kind tag -> (peak dense-bf16 TFLOP/s per chip, HBM GB/s, per-chip
# interconnect GB/s). Published per-chip numbers; the cpu row is a
# deliberately modest stand-in so CPU test runs still get finite
# MFU/verdicts (override via env for a specific host).
PEAK_TABLE = {
    "v4": (275.0, 1228.0, 300.0),
    "v5e": (197.0, 819.0, 200.0),
    "v5p": (459.0, 2765.0, 600.0),
    "v6e": (918.0, 1640.0, 448.0),
    "cpu": (0.2, 50.0, 10.0),
}

# device_kind substrings -> table tag, checked in order (a bare "v5"
# scan would alias v5p and v5e)
_KIND_TAGS = (
    (("v6e", "v6 lite", "trillium"), "v6e"),
    (("v5p",), "v5p"),
    (("v5e", "v5 lite", "v5lite"), "v5e"),
    (("v4",), "v4"),
)


def device_peaks():
    """The roofline ceilings for THIS process's default device:
    {"device_kind", "matched", "peak_tflops", "hbm_gbps",
    "ici_gbps"}. device_kind comes from PJRT; the CPU client takes
    the cpu row, and an accelerator whose kind is not in PEAK_TABLE
    raises — a utilization against a guessed peak is worse than none.
    PADDLE_PEAK_TFLOPS / PADDLE_HBM_GBPS / PADDLE_ICI_GBPS override
    individual legs — both the bench MFU column and the per-program
    MFU read THIS function, so the two can never disagree on the
    peak."""
    kind, platform = "cpu", "cpu"
    # evidence-gathering rule (shared with flight's dump path and
    # the /perfz handler): NEVER initialize a backend just to read
    # its kind — a debug page touching jax.devices() first could
    # pick a platform mid-rendezvous. Uninitialized reads as cpu.
    if _flight._jax_backends_live():
        import jax

        dev = jax.devices()[0]
        platform = dev.platform
        kind = str(dev.device_kind or platform)
    low = kind.lower()
    matched = "cpu" if platform == "cpu" else None
    for subs, tag in _KIND_TAGS:
        if any(s in low for s in subs):
            matched = tag
            break
    if matched is None:
        raise ValueError(
            f"device kind {kind!r} (platform {platform!r}) is not in "
            "monitor.perf.PEAK_TABLE — add its published peaks there")
    tf, hbm, ici = PEAK_TABLE[matched]
    return {
        "device_kind": kind,
        "matched": matched,
        "peak_tflops": _env_float("PADDLE_PEAK_TFLOPS", tf),
        "hbm_gbps": _env_float("PADDLE_HBM_GBPS", hbm),
        "ici_gbps": _env_float("PADDLE_ICI_GBPS", ici),
    }


def roofline_verdict(flops, bytes_accessed, peak_tflops, hbm_gbps,
                     comm_frac=0.0):
    """Classify one program against the roofline: "comm-bound" when
    the interconnect leg dominates the measured time (comm_frac >
    0.5 — the fleet's walls are elsewhere), else compare arithmetic
    intensity (flops/byte) with the machine balance
    (peak_flops / hbm_bandwidth): below balance the HBM leg caps the
    program, at/above it the MXUs do."""
    if comm_frac > 0.5:
        return "comm-bound"
    if not flops or not bytes_accessed:
        return "unknown"
    intensity = flops / float(bytes_accessed)
    balance = (peak_tflops * 1e12) / (hbm_gbps * 1e9)
    return "compute-bound" if intensity >= balance else "HBM-bound"


# ---------------------------------------------------------------------------
# The roofline report (CLI `perf`, bench extra.perf)
# ---------------------------------------------------------------------------

def _parse_program_gauges(stats):
    """{name: {flops, bytes_accessed, transcendentals}} out of the
    flat perf/program/<name>/<key> gauge namespace."""
    progs = {}
    prefix = "perf/program/"
    for k, v in (stats or {}).items():
        if not k.startswith(prefix):
            continue
        rest = k[len(prefix):]
        name, _, key = rest.rpartition("/")
        if name and key:
            progs.setdefault(name, {})[key] = v
    return progs


def _dispatch_snap(hists, name):
    """The program's dispatch histogram snapshot — shape-specialized
    `#N` ledger entries share their base name's histogram (one
    distribution per fn, like jit/<fn>/compile_us)."""
    snap = (hists or {}).get(f"jit/hist/{name}/dispatch_us")
    if snap is None and "#" in name:
        snap = (hists or {}).get(
            f"jit/hist/{name.split('#')[0]}/dispatch_us")
    return snap


def perf_report(stats=None, hists=None, peaks=None):
    """The full compute-attribution picture: the peak ceilings, the
    comm leg (wire bytes priced against the interconnect), and per
    program the cost ledger + measured dispatch quantiles + achieved
    FLOP/s, arithmetic intensity, MFU and roofline verdict. Reads
    the LIVE registries by default; pass a dump bundle's
    telemetry["stats"]/["hists"] for offline forensics (the CLI
    `perf <bundle>` path)."""
    if stats is None:
        stats = _cmon.registry.snapshot()
    if hists is None:
        hists = _cmon.registry.snapshot_histograms()
    if peaks is None:
        peaks = device_peaks()
    progs = _parse_program_gauges(stats)
    # total measured dispatch seconds across every program — the
    # denominator the comm leg is weighed against
    total_s = 0.0
    seen_hists = set()
    for name in progs:
        snap = _dispatch_snap(hists, name)
        if snap is not None and id(snap) not in seen_hists:
            seen_hists.add(id(snap))
            total_s += float(snap.get("sum", 0.0)) / 1e6
    wire = sum(v for k, v in (stats or {}).items()
               if k.startswith("comm/") and k.endswith("/wire_bytes"))
    comm_s = wire / (peaks["ici_gbps"] * 1e9) \
        if peaks["ici_gbps"] > 0 else 0.0
    comm_frac = comm_s / total_s if total_s > 0 else 0.0
    out_progs = {}
    for name in sorted(progs):
        cost = progs[name]
        flops = int(cost.get("flops", 0))
        ba = int(cost.get("bytes_accessed", 0))
        ent = {"flops": flops, "bytes_accessed": ba,
               "transcendentals": int(cost.get("transcendentals", 0)),
               "dispatch": None, "achieved_gflops": None,
               "intensity": None, "mfu": None,
               "verdict": roofline_verdict(
                   flops, ba, peaks["peak_tflops"],
                   peaks["hbm_gbps"], comm_frac)}
        if flops and ba:
            ent["intensity"] = round(flops / float(ba), 3)
        snap = _dispatch_snap(hists, name)
        if snap is not None and snap.get("count"):
            p50 = snapshot_quantile(snap, 0.5)
            ent["dispatch"] = {
                "count": int(snap["count"]),
                "p50_us": round(p50, 1),
                "p99_us": round(snapshot_quantile(snap, 0.99), 1),
            }
            if flops and p50 > 0:
                ach = flops / (p50 / 1e6)
                ent["achieved_gflops"] = round(ach / 1e9, 3)
                ent["mfu"] = round(
                    ach / (peaks["peak_tflops"] * 1e12), 4)
        out_progs[name] = ent
    return {
        "peaks": peaks,
        "comm": {"wire_bytes": int(wire),
                 "est_us": int(comm_s * 1e6),
                 "frac": round(comm_frac, 4)},
        "measured_total_us": int(total_s * 1e6),
        "programs": out_progs,
    }
