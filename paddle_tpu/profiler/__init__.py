"""paddle.profiler (reference: paddle/fluid/platform/profiler/ —
Profiler, RecordEvent, chrome-trace export; python/paddle/profiler/).

TPU-native: host events are the program spans of monitor.flight
(HostTracer analog; one ring, perf_counter, and the jax.profiler
host plane);
device timeline via jax.profiler (XPlane — the TPU-native equivalent of
CUPTI activity records), exportable to TensorBoard; chrome-trace JSON
export of host events for tools/timeline.py parity."""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time

from ..monitor import flight as _flight

__all__ = ["Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "start_profiler", "stop_profiler", "record_counter",
           "is_recording"]


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"
    CUSTOM_DEVICE = "custom"


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class _Recorder:
    """The capture a Profiler runs: whether one is on (shared by ALL
    threads), its counter time series, and its host spans. The spans
    are not kept here: RecordEvent goes through monitor.flight.span,
    and events() reads that one ring (every thread's spans, each with
    its tid), cut to the capture."""

    def __init__(self):
        self._lock = threading.Lock()
        self.active = False
        self._t0 = self._t1 = None   # perf_counter at start / stop
        self._counters = []  # (name, ts, value) time series (ph "C")

    def start(self):
        with self._lock:
            self._counters = []
            self._t0, self._t1 = time.perf_counter(), None
            self.active = True

    def stop(self):
        self.active = False
        self._t1 = time.perf_counter()

    def record_counter(self, name, value, ts=None):
        if not self.active:
            return
        with self._lock:
            self._counters.append(
                (name, ts if ts is not None else time.perf_counter(),
                 float(value)))

    def events(self):
        """(name, category, begin, end, tid, args) of every program
        span that began and ended inside the last capture, sorted by
        begin time; names without the `paddle_tpu/` prefix."""
        if self._t0 is None:
            return []
        t1 = self._t1 if self._t1 is not None else time.perf_counter()
        out = []
        for sp in _flight.spans(since=self._t0):
            if sp["start"] < self._t0 or sp["end"] > t1:
                continue
            args = sp["ids"]
            cat = args.pop("cat", "Program")
            out.append((sp["name"][len(_flight.SPAN_PREFIX):], cat,
                        sp["start"], sp["end"], sp["tid"], args))
        out.sort(key=lambda e: e[2])
        return out

    def counters(self):
        with self._lock:
            return list(self._counters)


_recorder = _Recorder()


def is_recording():
    """True while a Profiler is capturing (any thread)."""
    return _recorder.active


def record_counter(name, value, ts=None):
    """Record one sample of a numeric time series into the active
    capture; exported as a chrome-trace counter (ph "C") event so
    Perfetto draws it as a track alongside the spans. No-op when no
    profiler is running."""
    _recorder.record_counter(name, value, ts)


class RecordEvent:
    """RAII host-event annotation (reference: platform/profiler.h
    RecordEvent, used at every TraceOp). One program span
    (monitor.flight.span) named `paddle_tpu/<name>`: in the span ring
    always, in a Profiler's chrome-trace export under `name` with
    `event_type` as its category, and in the host plane of any
    jax.profiler trace. `args` (a small dict of scalars, e.g.
    {"batch_size": 32}) are the span's ids and export into the
    chrome-trace event's args field."""

    def __init__(self, name, event_type="UserDefined", args=None):
        self.name = name
        self.event_type = event_type
        self.args = args
        self._span = None

    def begin(self):
        self._span = _flight.span(self.name, cat=self.event_type,
                                  **(self.args or {})).begin()

    def end(self):
        if self._span is None:
            return
        self._span.end()
        self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Profiler step scheduler (reference: paddle.profiler
    make_scheduler). Cycles CLOSED->READY->RECORD(_AND_RETURN); with
    repeat > 0 the scheduler returns CLOSED permanently after `repeat`
    full cycles (previously the argument was accepted and ignored)."""
    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        cycle = closed + ready + record
        if repeat and cycle and s // cycle >= repeat:
            return ProfilerState.CLOSED
        pos = s % cycle if cycle else 0
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        prof.export(os.path.join(dir_name,
                                 (worker_name or "worker") + ".json"),
                    format="json")

    return handler


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, python_tracer=True):
        """python_tracer=False drops the per-python-frame device-plane
        events from the jax capture — on very large programs (e.g. a
        fully unrolled transformer) the python plane alone runs to ~1M
        events and crowds the XLA op plane out of the merged export."""
        self._targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._python_tracer = python_tracer
        self._step = 0
        self._jax_dir = None
        self._step_times = []
        self._last_step_t = None

    def start(self):
        _recorder.start()
        self._last_step_t = time.perf_counter()
        # host/device common epoch: device (XPlane) timestamps are
        # relative to trace start, so host events rebase onto the same
        # zero for ONE correlated timeline
        self._epoch = time.perf_counter()
        if ProfilerTarget.TPU in self._targets and not self._timer_only:
            import tempfile

            self._jax_dir = tempfile.mkdtemp(prefix="paddle_tpu_prof_")
            try:
                import jax

                opts = None
                if not self._python_tracer:
                    try:
                        opts = jax.profiler.ProfileOptions()
                        opts.python_tracer_level = 0
                    except Exception:
                        opts = None
                if opts is not None:
                    try:
                        jax.profiler.start_trace(self._jax_dir,
                                                 profiler_options=opts)
                    except TypeError:
                        # older jax: no profiler_options kwarg —
                        # passing it unconditionally used to kill the
                        # WHOLE device capture (the TypeError was
                        # swallowed and _jax_dir nulled)
                        jax.profiler.start_trace(self._jax_dir)
                else:
                    jax.profiler.start_trace(self._jax_dir)
            except Exception:
                self._jax_dir = None

    def stop(self):
        _recorder.stop()
        if self._jax_dir is not None:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            dt = now - self._last_step_t
            self._step_times.append(dt)
            # counter (ph "C") samples: the merged chrome trace shows
            # step time / throughput / device memory as tracks next to
            # the host spans (reference: the new profiler's
            # MemTraceEvent counters in ChromeTracingLogger). The
            # profiler/ prefix keeps this series on its OWN track —
            # monitor.StepTimer emits per-train-batch samples under the
            # bare names, and Profiler.step intervals have different
            # semantics (whatever the user brackets between steps)
            _recorder.record_counter("profiler/step_time_ms", dt * 1e3,
                                     ts=now)
            if num_samples:
                _recorder.record_counter("profiler/throughput",
                                         num_samples / dt, ts=now)
            try:
                from ..monitor import memory as _mem_mod

                # PJRT stats where available, live-array census
                # elsewhere (the CPU client) — so every backend gets
                # a memory track, not just TPU. PADDLE_MEM_STEP=0
                # disables here too (same knob as StepTimer: the
                # census walk is the cost being opted out of).
                used, peak = _mem_mod.step_reading()
            except Exception:
                used = peak = 0
            if used or peak:
                _recorder.record_counter(
                    "mem/allocated_bytes", used, ts=now)
                _recorder.record_counter(
                    "mem/peak_bytes", peak, ts=now)
                # legacy series names (pre-memory-module dashboards)
                _recorder.record_counter(
                    "profiler/device_mem_bytes_in_use", used, ts=now)
                _recorder.record_counter(
                    "profiler/device_mem_peak_bytes", peak, ts=now)
            try:
                # per-program roofline-ledger gauges (ISSUE 16): fold
                # perf/program/<name>/{flops,bytes_accessed,...} into
                # the counter stream so the merged Perfetto timeline
                # shows each program's FLOP/byte ledger as ph "C"
                # tracks next to the memory counters above
                from ..core import monitor as _cmon

                for name, value in _cmon.registry.snapshot().items():
                    if name.startswith("perf/program/"):
                        _recorder.record_counter(name, value, ts=now)
            except Exception:
                pass
        self._last_step_t = now
        self._step += 1

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        avg = sum(self._step_times) / len(self._step_times)
        return f"avg step time: {avg * 1000:.3f} ms"

    def export(self, path, format="json"):
        epoch = getattr(self, "_epoch", 0.0)
        events = []
        for name, cat, begin, end, tid, eargs in _recorder.events():
            ev = {
                "name": name, "cat": cat, "ph": "X",
                "ts": (begin - epoch) * 1e6,
                "dur": (end - begin) * 1e6,
                "pid": 0, "tid": tid,
            }
            if eargs:
                ev["args"] = dict(eargs)
            events.append(ev)
        # counter (ph "C") tracks: step time, throughput, device memory
        # samples recorded via record_counter fold into the SAME
        # timeline so Perfetto draws them alongside the spans
        events.extend({
            "name": name, "ph": "C",
            "ts": (ts - epoch) * 1e6,
            "pid": 0,
            "args": {"value": value},
        } for name, ts, value in _recorder.counters())
        # merged host+device timeline (reference: the new profiler's
        # EventNode trees combining HostTracer + CudaTracer into ONE
        # chrome trace): fold the XLA/device events jax.profiler
        # captured into the same traceEvents list, on separate pids
        events.extend(self._device_trace_events())
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)

    def _device_trace_events(self, pid_offset=1000):
        """Chrome-trace events from the jax.profiler (XPlane) capture,
        re-labeled onto device pids."""
        if self._jax_dir is None:
            return []
        import glob
        import gzip

        out = []
        pattern = os.path.join(self._jax_dir, "**", "*.trace.json.gz")
        for fp in glob.glob(pattern, recursive=True):
            try:
                with gzip.open(fp, "rt") as f:
                    trace = json.load(f)
            except (OSError, ValueError):
                continue
            for ev in trace.get("traceEvents", []):
                if not isinstance(ev, dict) or "ph" not in ev:
                    continue
                ev = dict(ev)
                if isinstance(ev.get("pid"), int):
                    ev["pid"] = ev["pid"] + pid_offset
                out.append(ev)
        return out

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        agg = {}
        for name, _, b, e, _, _a in _recorder.events():
            tot, cnt = agg.get(name, (0.0, 0))
            agg[name] = (tot + (e - b), cnt + 1)
        lines = [f"{'Event':40s} {'Calls':>8s} {'Total(ms)':>12s}"]
        for name, (tot, cnt) in sorted(agg.items(), key=lambda kv: -kv[1][0]):
            lines.append(f"{name:40s} {cnt:8d} {tot * 1000:12.3f}")
        # op-level dispatch stats when FLAGS_profile_ops was on
        # (ir/cost_model op stat table analog)
        from ..core import monitor as _mon

        op_stats = {k: v for k, v in _mon.registry.all().items()
                    if k.startswith("op/")}
        if op_detail and op_stats:
            lines.append("")
            lines.append(f"{'Op':40s} {'Calls':>8s} {'Host us':>12s}")
            ops = sorted({k.split('/')[1] for k in op_stats})
            for op in ops:
                calls = op_stats.get(f"op/{op}/calls", 0)
                us = op_stats.get(f"op/{op}/host_us", 0)
                lines.append(f"{op:40s} {calls:8d} {us:12d}")
        return "\n".join(lines)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)


_global_prof = None


def start_profiler(state="All", tracer_option="Default"):
    global _global_prof
    _global_prof = Profiler()
    _global_prof.start()


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    global _global_prof
    if _global_prof is not None:
        _global_prof.stop()
        _global_prof.export(profile_path + ".json")
        _global_prof = None
