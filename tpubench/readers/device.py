"""Readers that need the chip: memory, the analytic utilization, and the
profiler trace. On a CPU (toy configurations in the tests) they read
nothing."""
from __future__ import annotations



def peak_hbm_gib(run):
    if not run.on_tpu:
        return None
    return run.memory_peak_bytes() / 2 ** 30


def model_flops_util(run, flops_fn, seq="sequence_length"):
    """Operations the forward and backward passes need per token
    (`flops_fn`, a `module.function` under tpubench/models/) times the
    window's tokens per second per chip (the end-to-end rate), over the
    chip's bf16 peak."""
    if not run.on_tpu:
        return None
    from . import samples

    r = samples.window_rate(run)
    if r is None:
        return None
    per_token = _model_fn(flops_fn)(run.config, run.values[seq])
    return 100.0 * per_token * r / run.peaks()["bf16_flops_per_s"]


def idle_share(run):
    tr = run.reduced_trace() if run.on_tpu else None
    if tr is None or not tr.window_seconds():
        return None
    return 100.0 * (1.0 - tr.busy_seconds() / tr.window_seconds())


def exposed_collective_share(run):
    tr = run.reduced_trace() if run.on_tpu else None
    if tr is None or not tr.window_seconds():
        return None
    s = tr.exposed_collective_seconds()
    return None if s is None else 100.0 * s / tr.window_seconds()


def roofline(run, events, least_fn):
    """Least time from shapes over device time from the trace.

    events: {"group": g} sums the `XLA Ops` events of a breakdown group,
    {"module": prefix} the executed programs whose name starts so and, with
    "pick": "most_frequent", of those only the one program (name and hash)
    that ran most often. `least_fn`
    is a `module.function` under tpubench/models/ that gives the (flops,
    bytes) those events cannot do without; the least time is the larger of
    flops over the bf16 peak and bytes over the HBM peak. Over 100 % means the
    operations or bytes are counted too high, or the time leaves out part of
    the work."""
    tr = run.reduced_trace() if run.on_tpu else None
    if tr is None:
        return None
    chip = min(tr.devices)
    evs = tr.group_events(events["group"], chip) if "group" in events \
        else tr.module_events(events["module"], chip,
                              events.get("pick") == "most_frequent")
    if not evs:
        return None
    need = _model_fn(least_fn)(run, len(evs))
    if need is None:
        return None
    p = run.peaks()
    least = max(need[0] / p["bf16_flops_per_s"],
                need[1] / p["hbm_bytes_per_s"])
    return 100.0 * least / sum(b - a for a, b in evs)


def _model_fn(path):
    """`module.function` under tpubench/models/."""
    import importlib

    module, _, fn = path.partition(".")
    return getattr(importlib.import_module(f"tpubench.models.{module}"), fn)
