"""paddle_tpu.monitor.memory — device-memory observability + OOM
forensics.

The reference framework tracks process-wide GPU memory through
allocator hooks (platform/monitor.h StatValue gpu_mem stats, the
paddle/fluid/memory facade, and paddle.device.cuda.memory_allocated /
max_memory_allocated on top). On TPU the allocator belongs to PJRT, so
this module reads memory three ways instead of hooking allocations:

  * device stats — PJRT `device.memory_stats()` where the backend
    exposes it (TPU does; the CPU client usually doesn't), with a
    fallback that accounts bytes via a `jax.live_arrays()` census.
    Surfaced as `paddle.device.memory_allocated()` /
    `max_memory_allocated()` / `reset_max_memory_allocated()` /
    `memory_stats()` and the monitor gauges
    `mem/{allocated,peak}_bytes` (synced by `telemetry_snapshot()`).

  * live-array census — `live_array_census()` groups every live jax
    array by (shape, dtype) and reports bytes + count per group,
    NEVER array contents. This is the "what is holding HBM" answer a
    RESOURCE_EXHAUSTED post-mortem needs.

  * per-program footprints — jit records each compiled program's
    `memory_analysis()` (argument/output/temp/generated-code bytes)
    through `record_program_memory()`; gauges land under
    `mem/program/<fn>/...` and `jit.cache_report()` carries the same
    numbers into every flight dump bundle.

OOM forensics: `is_oom_error()` classifies JaxRuntimeError
RESOURCE_EXHAUSTED; `oom_observer()` (auto-armed by `hapi.Model.fit`)
writes an "oom" flight bundle whose memory section holds device
stats, per-program footprints and the top-K census before re-raising;
the flight excepthook classifies the same way for uncaught OOMs.

Env knobs: PADDLE_MEM_CENSUS_TOP_K (census groups in reports/dumps,
default 15), PADDLE_MEM_PROGRAM (0 disables per-program
memory_analysis capture at jit build — it costs one extra XLA
backend compile per program), PADDLE_MEM_STEP (0 disables the
per-step StepTimer memory gauges/counters).
"""
from __future__ import annotations

import contextlib
import threading

from ..core import monitor as _cmon
from ..core.place import Place as _Place
from ..core.place import device_of as _place_device_of
from .flight import _env_int, _env_on  # shared env-parsing semantics

__all__ = [
    "memory_allocated", "max_memory_allocated",
    "reset_max_memory_allocated", "memory_stats",
    "live_array_census", "sync_gauges", "record_program_memory",
    "extract_memory_analysis",
    "program_capture_enabled", "step_tracking_enabled",
    "step_reading",
    "program_footprints", "memory_report", "memory_section",
    "is_oom_error", "is_compile_oom_error", "oom_observer",
    "auto_oom_observer",
    "census_top_k",
]


def census_top_k():
    """Census groups embedded in reports/dump bundles
    (PADDLE_MEM_CENSUS_TOP_K, default 15; <= 0 means unlimited)."""
    return _env_int("PADDLE_MEM_CENSUS_TOP_K", 15)


def program_capture_enabled():
    """PADDLE_MEM_PROGRAM gate for memory_analysis capture at jit
    build. Default on; the capture costs one extra XLA backend
    compile per program (the lowering is shared, the backend pass is
    not), so huge-model users can switch it off."""
    return _env_on("PADDLE_MEM_PROGRAM", True)


def step_tracking_enabled():
    """PADDLE_MEM_STEP gate for the per-step StepTimer memory gauges
    (a census walk per step on backends without PJRT stats)."""
    return _env_on("PADDLE_MEM_STEP", True)


def step_reading():
    """(allocated, peak) bytes for per-step tracking — the shared
    body of StepTimer.end_step and Profiler.step: one memory_stats()
    walk, (0, 0) when PADDLE_MEM_STEP=0 or the reading fails (a
    half-initialized backend must not break a training step)."""
    if not step_tracking_enabled():
        return 0, 0
    try:
        stats = memory_stats()
        return stats["allocated_bytes"], stats["peak_bytes"]
    except Exception:
        return 0, 0


# ---------------------------------------------------------------------------
# Device stats (PJRT, census fallback) + peak tracking
# ---------------------------------------------------------------------------

_peak_lock = threading.Lock()
# per-device watermarks, keyed by str(resolved device):
# [peak_bytes, reset_seen]. reset_seen=True means PJRT's own
# monotonic peak_bytes_in_use predates the reset, so only locally
# observed values feed that device's watermark from then on.
_peaks = {}


def _observe(key, allocated, pjrt_peak=None):
    """Fold one allocated-bytes observation (plus PJRT's own peak
    when trustworthy) into the device's watermark."""
    with _peak_lock:
        ent = _peaks.setdefault(key, [0, False])
        cand = int(allocated)
        if pjrt_peak and not ent[1]:
            cand = max(cand, int(pjrt_peak))
        if cand > ent[0]:
            ent[0] = cand
        return ent[0]


def _census_total(device=None):
    """Total bytes across jax.live_arrays() — the allocated-bytes
    fallback where PJRT exposes no memory stats. With `device`, only
    bytes resident on that device count (per-shard for multi-device
    arrays), so a forced multi-device host (e.g.
    --xla_force_host_platform_device_count=N) gets real per-device
    numbers instead of N copies of the process-global total."""
    import jax

    total = 0
    for a in jax.live_arrays():
        try:
            if device is None:
                total += int(a.nbytes)
                continue
            devs = a.devices()
            if device not in devs:
                continue
            if len(devs) == 1:
                total += int(a.nbytes)
            else:
                total += sum(int(s.data.nbytes)
                             for s in a.addressable_shards
                             if s.device == device)
        except Exception:
            pass  # an array mid-deletion must not kill accounting
    return total


def _resolve_device(device):
    """Resolve a reference-API device specifier — None, an ordinal
    int, a Place, or a "tpu:0"/"gpu:1"/"cpu"-style string — to a
    jax Device,
    so memory_allocated(0) or memory_allocated("tpu:0") reads the
    real device instead of silently accounting nothing against a
    bogus string-keyed watermark. jax Devices pass through."""
    import jax

    if device is None:
        return jax.devices()[0]
    if isinstance(device, bool):
        raise TypeError(f"invalid device specifier: {device!r}")
    if isinstance(device, _Place):
        # the package's own Place objects (what get_device_place()
        # returns) resolve through the device-context pool so the
        # accounted device is the SAME one tensor placement uses
        return _place_device_of(device)
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str):
        plat, _, idx = device.partition(":")
        if plat.isdigit() and not idx:
            return jax.devices()[int(plat)]
        # honor the platform leg: "cpu" on a TPU host must read the
        # host client, not silently alias devices()[0] (jax raises
        # on a platform the process has no client for — a clear
        # error beats bytes from the wrong device)
        devs = jax.devices(plat) if plat else jax.devices()
        return devs[int(idx) if idx else 0]
    return device


def _read(device):
    """One reading: (watermark key, allocated bytes, PJRT peak or
    None, raw PJRT stat dict, source). Resolves device=None (and
    int/string specifiers) to a jax Device up front so explicit
    jax.devices()[0], "tpu:0", 0 and None share one watermark."""
    dev = _resolve_device(device)
    raw = _cmon.device_memory_stats(dev)
    if raw.get("bytes_in_use") is not None:
        return (str(dev), int(raw["bytes_in_use"]),
                raw.get("peak_bytes_in_use"), raw, "pjrt")
    return str(dev), _census_total(dev), None, raw, "census"


def memory_allocated(device=None):
    """Bytes currently allocated on the device (reference:
    paddle.device.cuda.memory_allocated). PJRT `bytes_in_use` where
    available, else the live-array census total."""
    key, used, pjrt_peak, _, _ = _read(device)
    _observe(key, used, pjrt_peak)
    return used


def max_memory_allocated(device=None):
    """High-water mark of allocated bytes since process start or the
    last reset_max_memory_allocated() (reference:
    paddle.device.cuda.max_memory_allocated). Per device. Seeded
    from PJRT's peak_bytes_in_use until a reset; after a reset it
    tracks the max of values observed by this module (PJRT peaks are
    monotonic and cannot be reset from the client)."""
    key, used, pjrt_peak, _, _ = _read(device)
    return _observe(key, used, pjrt_peak)


def reset_max_memory_allocated(device=None):
    """Reset the device's tracked high-water mark to its CURRENT
    allocated bytes (reference:
    paddle.device.cuda.reset_max_memory_allocated). Returns the new
    watermark."""
    key, used, _, _, _ = _read(device)
    with _peak_lock:
        _peaks[key] = [used, True]
        return used


def memory_stats(device=None):
    """Full device-memory stat dict: the raw PJRT stats (when the
    backend has them) plus the normalized keys every backend gets —
    `allocated_bytes`, `peak_bytes` (this module's resettable
    watermark) and `source` ("pjrt" | "census"). One reading — use
    this (not allocated+max back to back) on hot paths: the census
    fallback walks every live array per reading."""
    key, used, pjrt_peak, raw, source = _read(device)
    peak = _observe(key, used, pjrt_peak)
    out = dict(raw) if source == "pjrt" else {}
    out.update({"source": source, "allocated_bytes": used,
                "peak_bytes": peak})
    return out


def sync_gauges():
    """Push the device memory numbers into the StatRegistry
    (mem/allocated_bytes, mem/peak_bytes) — called by
    monitor.telemetry_snapshot() so exporter flushes, bench records
    and dump bundles always carry fresh values."""
    stats = memory_stats()
    used, peak = stats["allocated_bytes"], stats["peak_bytes"]
    _cmon.stat_set("mem/allocated_bytes", used)
    _cmon.stat_set("mem/peak_bytes", peak)
    return used, peak


# ---------------------------------------------------------------------------
# Live-array census
# ---------------------------------------------------------------------------

def live_array_census(top_k=None):
    """Group every live jax array by (shape, dtype): bytes + count
    per group, sorted by bytes descending — never array CONTENTS.
    `top_k` caps the group list (None -> PADDLE_MEM_CENSUS_TOP_K;
    <= 0 -> unlimited). Totals always cover every live array, so a
    truncated report still accounts all bytes."""
    import jax

    if top_k is None:
        top_k = census_top_k()
    groups = {}
    total_bytes = 0
    total_arrays = 0
    for a in jax.live_arrays():
        try:
            key = (tuple(a.shape), str(a.dtype))
            nbytes = int(a.nbytes)
        except Exception:
            continue  # mid-deletion array
        total_arrays += 1
        total_bytes += nbytes
        ent = groups.get(key)
        if ent is None:
            groups[key] = [1, nbytes]
        else:
            ent[0] += 1
            ent[1] += nbytes
    ranked = sorted(groups.items(), key=lambda kv: -kv[1][1])
    n_groups = len(ranked)
    if top_k and top_k > 0:
        ranked = ranked[:top_k]
    return {
        "total_bytes": total_bytes,
        "total_arrays": total_arrays,
        "group_count": n_groups,
        "truncated": n_groups > len(ranked),
        "groups": [{"shape": list(shape), "dtype": dtype,
                    "count": cnt, "bytes": nbytes}
                   for (shape, dtype), (cnt, nbytes) in ranked],
    }


# ---------------------------------------------------------------------------
# Per-program footprints (fed by jit at build time)
# ---------------------------------------------------------------------------

_MEM_FIELDS = (
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
)


def extract_memory_analysis(compiled):
    """`compiled.memory_analysis()` as a plain byte dict (None when
    the backend exposes no analysis). `compiled` is a
    jax.stages.Compiled (or anything with .memory_analysis())."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for key, attr in _MEM_FIELDS:
        try:
            out[key] = int(getattr(ma, attr))
        except (AttributeError, TypeError):
            out[key] = 0
    # XLA's own peak-usage identity: arguments + outputs + temps +
    # generated code, minus buffers aliased into the arguments
    out["total_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                          + out["temp_bytes"]
                          + out["generated_code_bytes"]
                          - out["alias_bytes"])
    return out


def record_program_memory(name, compiled):
    """extract_memory_analysis() plus the `mem/program/<name>/...`
    gauge writes — what the jit build path calls per fresh cache
    entry."""
    out = extract_memory_analysis(compiled)
    if out is None:
        return None
    for key in ("argument_bytes", "output_bytes", "temp_bytes",
                "generated_code_bytes", "total_bytes"):
        _cmon.stat_set(f"mem/program/{name}/{key}", out[key])
    return out


def program_footprints(report=None):
    """Per-program memory analyses off the live jit caches (the same
    numbers jit.cache_report() embeds) — {name: byte dict}. Pass a
    precomputed cache_report() list as `report` to skip the live-
    compiler walk (dump bundles already hold one for jit_caches)."""
    if report is None:
        try:
            from .. import jit as _jit

            report = _jit.cache_report()
        except Exception:
            return {}
    out = {}

    def _put(name, m):
        # two live compilers can share kind:fn (e.g. the fused and
        # tail train_step siblings over one model class) — suffix
        # instead of overwriting so neither footprint is dropped
        key, n = name, 2
        while key in out:
            key = f"{name}({n})"
            n += 1
        out[key] = m

    for ent in report:
        mem = ent.get("memory")
        if not mem:
            continue
        name = f"{ent.get('kind')}:{ent.get('fn')}"
        if isinstance(mem, list):
            for i, m in enumerate(mem):
                if m:
                    # entry 0 keeps the plain name — same ordinal
                    # scheme as the mem/program/<fn>[#N]/* gauges, so
                    # bundle footprints and exporter gauges match by
                    # name
                    _put(name if i == 0 else f"{name}#{i}", m)
        else:
            _put(name, mem)
    return out


# ---------------------------------------------------------------------------
# Reports / dump-bundle section
# ---------------------------------------------------------------------------

def memory_report(top_k=None):
    """The full live picture: device stats + per-program footprints +
    the live-array census. What `python -m paddle_tpu.monitor memory`
    prints and what an OOM bundle embeds. Degrades to
    {"uninitialized": True} before any jax backend is live — this is
    an evidence-gathering path (the /memz handler thread, pre-init
    REPL hooks) and must never be the thing that initializes a
    backend."""
    from . import flight as _flight

    if not _flight._jax_backends_live():
        return {"uninitialized": True}
    return {"device": memory_stats(),
            "programs": program_footprints(),
            "census": live_array_census(top_k)}


def memory_section(census=True, jit_report=None):
    """The `memory` key of a flight dump bundle. Census is included
    for OOM/live-inspection dumps; watchdog/crash bundles keep the
    cheap device + program half only unless asked. `jit_report`
    forwards a precomputed cache_report() to program_footprints()."""
    try:
        out = {"device": memory_stats(),
               "programs": program_footprints(jit_report)}
        if census:
            out["census"] = live_array_census()
        return out
    except Exception as e:  # forensics must never break the dump
        return {"error": f"{type(e).__name__}: {e}"}


# ---------------------------------------------------------------------------
# OOM classification + observer
# ---------------------------------------------------------------------------

def is_oom_error(exc):
    """True when `exc` is the XLA runtime's RESOURCE_EXHAUSTED (the
    HBM-exhaustion crash on TPU): a jax.errors.JaxRuntimeError whose
    message carries the status code or the BFC allocator's
    `Out of memory` text."""
    import jax

    if not isinstance(exc, jax.errors.JaxRuntimeError):
        return False
    msg = str(exc)
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg


def is_compile_oom_error(exc):
    """True when the out-of-memory `exc` (is_oom_error) came from the
    compiler: the program itself does not fit the device (XLA:TPU
    "compile permanent error. Ran out of memory in memory space hbm"),
    whatever is live on it, so evicting live buffers cannot cure it."""
    msg = str(exc)
    return is_oom_error(exc) and (
        "ompile" in msg or "Ran out of memory in memory space" in msg)


@contextlib.contextmanager
def oom_observer(reason="oom"):
    """Context manager that turns a RESOURCE_EXHAUSTED crash into a
    forensics bundle WITH the memory section (device stats, per-
    program footprints, top-K live-array census — taken while the
    arrays that caused the OOM are still live), then re-raises.
    Auto-armed around the `hapi.Model.fit` train loop; the flight
    excepthook skips re-dumping an exception this observer already
    bundled."""
    try:
        yield
    except Exception as e:
        if is_oom_error(e) and not getattr(
                e, "_paddle_flight_dumped", False):
            try:
                from . import flight as _flight
                import sys

                _flight.record("oom", message=str(e)[:300])
                # write_dump builds the memory section itself;
                # full_memory=True keeps the census (taken HERE,
                # while the offending arrays are still live) even
                # when the caller renamed the reason
                _flight.write_dump(
                    reason, full_memory=True,
                    extra={"exception": _flight._format_exception(
                        *sys.exc_info())})
                try:
                    e._paddle_flight_dumped = True
                except Exception:
                    pass
            except Exception:
                pass  # forensics must not mask the original OOM
        raise


def auto_oom_observer():
    """What `hapi.Model.fit` wraps the train loop in: oom_observer()
    unless the operator explicitly disabled flight auto-arming
    (PADDLE_FLIGHT_AUTOARM set falsy — the same off switch
    flight.maybe_auto_arm honors). Unlike maybe_auto_arm's unset
    default (distributed runs only), OOM bundles default ON even
    single-host: an OOM is exactly the failure a notebook user wants
    evidence for, and the observer costs nothing until one fires.
    Explicit oom_observer() calls are never gated."""
    if _env_on("PADDLE_FLIGHT_AUTOARM", True):
        return oom_observer()
    return contextlib.nullcontext()
