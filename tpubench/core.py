"""The harness: one run of one cell. Everything that belongs to one
configuration, one traffic mix or one metric is a file found by its name in
BENCHMARK.json; nothing here names a cell.
"""
from __future__ import annotations

import contextlib
import fnmatch
import glob
import importlib
import json
import os
import shutil
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "tpubench")
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of BENCHMARK.json's `workloads`, with its files loaded."""

    def __init__(self, name, root=ROOT):
        self.bench = load_json(root, "BENCHMARK.json")
        by_name = {w["name"]: w for w in self.bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"tpubench: no workload {name!r}; have "
                             f"{sorted(by_name)}")
        self.entry = by_name[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(root, cfg["file"])
        self.traffic = load_json(root, "tpubench", "traffic",
                                 self.entry["traffic"] + ".json")
        self.root = root

    def metrics(self, group):
        """The metrics of `end_to_end` or `per_layer` that this cell reports,
        each with its own file's reader."""
        out = []
        for m in self.bench[group]:
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            spec = load_json(self.root, "tpubench", "metrics",
                             m["name"] + ".json")
            out.append((m, spec))
        return out


class Run:
    """What a kind's runner fills in and the readers read."""

    def __init__(self, cell, seed, seconds, trace, out_dir, t_start):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.out_dir = out_dir
        self.t_start = t_start       # perf_counter at process start
        self.spans = []              # (name, start, end), perf_counter
        self.samples = {}            # name -> list of numbers
        self.values = {}             # name -> number
        self.counters = {}           # "open"/"close" -> {name: value}
        self.window = None           # (start, end), perf_counter
        self.trace_file = None
        self._trace_reduced = None
        self._trace_dir = os.path.join(out_dir, "trace")
        self._tracing = None
        self._compiles = {"bench/xla_lowerings": 0,
                          "bench/backend_compiles": 0}
        self.device = None
        self.on_tpu = False
        self.correct = False
        self.attempted = 0
        self.failed = 0

    # -- devices ------------------------------------------------------------
    def claim_devices(self):
        """The device as JAX reports it. A measurement without a TPU, or with
        fewer chips than the cell asks for, ends here."""
        import jax

        devs = jax.devices()
        d = devs[0]
        self.on_tpu = d.platform == "tpu"
        self.device = {"platform": d.platform, "kind": d.device_kind,
                       "count": len(devs)}
        if not self.on_tpu and not (d.platform == "cpu"
                                    and self.config.get("cpu_toy") is True):
            raise SystemExit(f"tpubench: needs a TPU, JAX found "
                             f"{self.device}")
        if len(devs) < self.chips:
            raise SystemExit(f"tpubench: {self.cell.name} needs "
                             f"{self.chips} chips, JAX found {len(devs)}")
        if self.on_tpu:
            from paddle_tpu.jit import persistent_cache

            say(f"compile cache at {persistent_cache.arm_native()}")
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == LOWERING:
            self._compiles["bench/xla_lowerings"] += 1
        elif event == BACKEND_COMPILE:
            self._compiles["bench/backend_compiles"] += 1

    def peaks(self):
        table = load_json(HERE, "peaks.json")
        kind = self.device["kind"]
        if kind not in table:
            raise KeyError(f"no peaks for device kind {kind!r} in "
                           "tpubench/peaks.json")
        return table[kind]

    def memory_peak_bytes(self):
        """PJRT's peak_bytes_in_use of the fullest chip, plus the largest
        temporary allocation of a compiled program the program recorded
        (mem/program/*/temp_bytes): on this runtime PJRT's peak counts live
        buffers and leaves out an executable's temporaries (a train step
        that needs 9.7 GiB of them reads 4.8 GiB; PERF.md, PR 23)."""
        import jax
        from paddle_tpu.core.monitor import registry

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:self.chips]]
        temps = [v for k, v in registry.snapshot().items()
                 if fnmatch.fnmatchcase(k, "mem/program/*/temp_bytes")]
        return max(peaks) + max(temps, default=0)

    # -- spans and counters ---------------------------------------------------
    @contextlib.contextmanager
    def span(self, name):
        """A benchmark span around a call into a layer: kept in memory, and
        written into the profiler's trace when one is being taken."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("tpubench/" + name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def snapshot(self, label):
        from paddle_tpu.core.monitor import registry

        snap = dict(registry.snapshot())
        snap.update(self._compiles)
        self.counters[label] = snap

    def counter_delta(self, pattern, since="open", until="close"):
        a, b = self.counters[since], self.counters[until]
        return sum(v - a.get(k, 0) for k, v in b.items()
                   if fnmatch.fnmatchcase(k, pattern))

    def open_window(self):
        self.snapshot("open")
        t = time.perf_counter()
        self.values["setup_s"] = t - self.t_start
        return t

    def close_window(self, t_open, t_close):
        self.snapshot("close")
        self.window = (t_open, t_close)
        lo = self.counter_delta("bench/xla_lowerings")
        miss = self.counter_delta("jit/*/cache_miss")
        say(f"in the window: {lo} XLA lowerings, "
            f"{self.counter_delta('bench/backend_compiles')} backend "
            f"compiles, {miss} jit cache misses")

    # -- the profiler ---------------------------------------------------------
    def trace_due(self, elapsed):
        """True once, when a traced run reaches the last `trace_seconds` of
        its window."""
        if not self.trace or self._tracing is not None:
            return False
        return elapsed >= self.seconds - float(
            self.traffic.get("trace_seconds", 4))

    def start_trace(self):
        import jax

        shutil.rmtree(self._trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._tracing = jax.profiler.TraceAnnotation("tpubench/trace_window")
        self._tracing.__enter__()

    def stop_trace(self):
        import jax

        if self._tracing is None:
            return
        self._tracing.__exit__(None, None, None)
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self._trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        self.trace_file = found[0] if found else None

    def reduced_trace(self):
        """The trace reduced to events, read once; None without a trace."""
        if self._trace_reduced is None and self.trace_file:
            from .xplane import Trace

            self._trace_reduced = Trace.from_file(self.trace_file)
        return self._trace_reduced

    def drop_trace(self):
        shutil.rmtree(self._trace_dir, ignore_errors=True)

    # -- logs -----------------------------------------------------------------
    def write_log(self, name, rows):
        """One JSON object per line, in the run's output directory."""
        os.makedirs(self.out_dir, exist_ok=True)
        with open(os.path.join(self.out_dir, name), "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


def say(msg):
    print(f"[tpubench] {msg}", flush=True)


def read_metric(run, spec):
    """A metric's value by its file's named reader: `module.function` under
    tpubench/readers/. A reader that finds nothing to read returns None."""
    module, _, fn = spec["reader"].partition(".")
    reader = getattr(importlib.import_module(f"tpubench.readers.{module}"),
                     fn)
    return reader(run, **spec.get("args", {}))


def family(config):
    return importlib.import_module(f"tpubench.models.{config['family']}")


def kind(traffic):
    return importlib.import_module(f"tpubench.kinds.{traffic['kind']}")


def result_line(run):
    """The one JSON object the driver reads."""
    group = "per_layer" if run.trace else "end_to_end"
    metrics = {}
    for entry, spec in run.cell.metrics(group):
        value = read_metric(run, spec)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    device = dict(run.device)
    device["memory_peak_bytes"] = run.memory_peak_bytes()
    line = {"correct": bool(run.correct), "attempted": int(run.attempted),
            "failed": int(run.failed), "metrics": metrics, "device": device}
    tr = run.reduced_trace() if run.trace else None
    if tr is not None and tr.window is not None and tr.devices:
        device["busy_s"] = tr.busy_seconds()
        device["window_s"] = tr.window_seconds()
        line["breakdown"] = tr.breakdown()
    return line
