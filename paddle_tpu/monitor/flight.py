"""paddle_tpu.monitor.flight — black-box flight recorder + hang/crash
forensics.

The reference stack diagnoses wedged or dead distributed runs from
artifacts (VLOG trails, per-op timelines, the distributed hang dumps
around collective ops); this module is that capability for the TPU
stack, distinct from the opt-in profiler: it is ALWAYS on, cheap
enough to leave armed in production, and it answers "what were the
last things this rank did" after the fact.

Five pieces:

  * program spans — span(name, **ids) is THE span primitive of the
    program: one call feeds a bounded ring of closed spans on
    time.perf_counter() (read with spans(); name, start, end, the
    span that caused it, ids) and a jax.profiler.TraceAnnotation
    "paddle_tpu/<layer>/<what>", so whenever any profiler session
    runs the span lies in the trace's host plane on the device's
    clock. in_flight()/begin()/end() and profiler.RecordEvent go
    through it. Layers: compile, train, serve, io, comm. A span
    where the host only waits for the device is opened with
    wait_span(): the same span, whose ring record also says what the
    host's scheduler did to it meanwhile (runq_us, pressure_us:
    Linux, each where the host keeps it).

  * FlightRecorder — a process-wide bounded ring of structured events
    (step begin/end, jit cache miss, compile begin/end, collective
    begin/end with op/group/bytes, io fetch, exception, dump), fed by
    the same layers the monitor counters instrument. Appending is one
    lock + deque append (registry gauges amortize to every 256th
    event); the ring drops the oldest event when full and counts
    drops under flight/ring/dropped.

  * in-flight registry + Watchdog — begin()/end() (or the in_flight()
    context manager) mark a thread inside a potentially-blocking
    operation (collective, compile). The watchdog thread scans the
    registry and, once an entry exceeds PADDLE_WATCHDOG_TIMEOUT_S,
    writes a per-rank dump (all-thread stacks, the flight-ring tail,
    telemetry snapshot) instead of letting the slice hang silently —
    asymmetric collective participation is the dominant multi-slice
    failure mode (EQuARX; PAPERS.md).

  * dump bundles — write_dump() produces one JSON file per incident
    (schema "paddle_tpu.flight/1"): reason, rank/pid/host, env,
    device info, in-flight ops, per-thread stacks, flight tail,
    telemetry snapshot, jit program-cache keys. install_excepthook()
    writes one on any unhandled exception; dump_on_crash() is the
    context-manager flavor for worker threads; install_signal_handler
    wires SIGUSR1 for live dumps of a healthy-looking run.

  * arming — arm() switches everything on; maybe_auto_arm() is called
    from hapi.Model.fit and distributed.init_parallel_env and arms by
    default for distributed runs (PADDLE_TRAINERS_NUM > 1), gated by
    PADDLE_FLIGHT_AUTOARM=0/1.

Counters (exporter + bench.py pick these up with every snapshot):
flight/events, flight/ring/dropped, flight/spans,
flight/spans/dropped, flight/watchdog/fires, flight/dumps_written,
flight/watchdog/errors.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import os
import signal
import socket
import sys
import threading
import time
import traceback
from collections import deque

from jax.profiler import TraceAnnotation

from ..core import monitor as _cmon
from . import sanitize as _sanitize

__all__ = [
    "DUMP_SCHEMA", "FlightRecorder", "recorder", "record", "tail",
    "sync_stats", "SPAN_PREFIX", "Span", "span", "wait_span", "spans",
    "closed_span",
    "begin", "end", "in_flight", "inflight_snapshot", "Watchdog",
    "start_watchdog", "stop_watchdog", "get_watchdog", "write_dump",
    "dump_dir", "install_excepthook", "uninstall_excepthook",
    "dump_on_crash", "install_signal_handler",
    "uninstall_signal_handler", "arm", "maybe_auto_arm",
    "add_incident_hook", "remove_incident_hook",
]

DUMP_SCHEMA = "paddle_tpu.flight/1"


# ONE home for the env-knob parsers (the PR-13 dedup discipline),
# shared with core.monitor's Histogram config — aliased here because
# every monitor-side consumer (chaos, trace, fleet, serving) reaches
# them as flight._env_*
_env_int = _cmon._env_int
_env_float = _cmon._env_float


_FALSY = ("0", "false", "off", "no")


def _env_on(name, default=True):
    v = os.environ.get(name, "").strip().lower()
    if not v:
        return default
    return v not in _FALSY


def _jax_backends_live():
    """distributed.env._jax_ready with a total fallback — evidence
    gathering must not MUTATE backend state (see env.py), and must
    survive a half-broken package."""
    try:
        from ..distributed.env import _jax_ready

        return _jax_ready()
    except Exception:
        return False


def _rank():
    """distributed.env.peek_rank — the side-effect-free rank (never
    initializes a jax backend; never raises) — with a total fallback
    for crash paths where the distributed package itself may be
    broken. Lazy import: the distributed package must not load just
    because flight did."""
    try:
        from ..distributed.env import peek_rank

        return int(peek_rank())
    except Exception:
        try:
            return int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        except ValueError:
            return 0


def _world_size():
    """distributed.env.peek_world_size (side-effect-free; by fit/init
    time backends are live, so jax-native multi-host launches still
    auto-arm), with the same total fallback as _rank."""
    try:
        from ..distributed.env import peek_world_size

        return int(peek_world_size())
    except Exception:
        try:
            return int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
        except ValueError:
            return 1


def dump_dir():
    """Where watchdog/crash/signal bundles land: PADDLE_FLIGHT_DIR, or
    <tmp>/paddle_tpu_flight. Read per dump (not cached) so tests and
    late launcher setup can redirect it."""
    d = os.environ.get("PADDLE_FLIGHT_DIR")
    if not d:
        import tempfile

        d = os.path.join(tempfile.gettempdir(), "paddle_tpu_flight")
    return d


# ---------------------------------------------------------------------------
# Flight recorder ring
# ---------------------------------------------------------------------------

SPAN_PREFIX = "paddle_tpu/"
SPAN_CAPACITY = 32768


class FlightRecorder:
    """Bounded ring of (ts, tid, kind, data) events, and beside it the
    bounded ring of closed program spans.

    record() is the always-on hot path: one lock acquisition, one
    deque append, one stat bump — cheap enough to ride every jit cache
    hit and collective. PADDLE_FLIGHT_ENABLE=0 turns the whole layer
    (ring, in-flight registry, watchdog evidence) off;
    PADDLE_FLIGHT_CAPACITY sizes the ring (default 4096 events)."""

    def __init__(self, capacity=None, enabled=None):
        if capacity is None:
            capacity = _env_int("PADDLE_FLIGHT_CAPACITY", 4096)
        if enabled is None:
            enabled = _env_on("PADDLE_FLIGHT_ENABLE", True)
        self._ring = deque(maxlen=max(16, int(capacity)))
        # sanitize-aware (PADDLE_SANITIZE=locks): a plain Lock when
        # disarmed — record() is the always-on hot path
        self._lock = _sanitize.lock("flight.ring")
        self._seq = 0
        self._dropped = 0
        # closed program spans (see span()). A fixed size, not a knob:
        # a whole run of the longest benchmark cell, set-up included,
        # closes a few thousand spans. Appended without the lock:
        # deque.append and next() on a count are atomic (the total
        # may lag by a span while two threads close at once), and the
        # dropped figure is closed spans minus what the ring holds
        self._spans = deque(maxlen=SPAN_CAPACITY)
        self._spans_closed = itertools.count(1)
        self._n_spans = 0
        self.enabled = bool(enabled)

    @property
    def capacity(self):
        return self._ring.maxlen

    def record(self, kind, **data):
        if not self.enabled:
            return
        ev = (time.time(), threading.get_ident(), kind, data or None)
        with self._lock:
            self._seq += 1
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
            self._ring.append(ev)
            sync = self._seq % 256 == 0
        # registry gauges amortize to every 256th event: a per-event
        # stat_add would DOUBLE the hot-path cost once the ring fills
        # (every append then drops); telemetry_snapshot() syncs too,
        # so exporter flushes and dump bundles are always fresh
        if sync:
            self.sync_stats()

    def sync_stats(self):
        """Push the ring's internal counters into the StatRegistry
        (flight/events, flight/ring/dropped)."""
        with self._lock:
            seq, dropped = self._seq, self._dropped
        _cmon.stat_set("flight/events", seq)
        _cmon.stat_set("flight/ring/dropped", dropped)
        st = self.span_stats()
        _cmon.stat_set("flight/spans", st["closed"])
        _cmon.stat_set("flight/spans/dropped", st["dropped"])

    def record_span(self, rec):
        """Append one closed span (Span.end's tuple) to the span ring."""
        self._spans.append(rec)
        self._n_spans = next(self._spans_closed)

    def spans(self, since=None, last=None):
        """The closed spans the ring still holds, in the order they
        closed, as dicts: id, parent (0 for a thread's outermost),
        tid, name, start and end (time.perf_counter() seconds), ids.
        `since` keeps the spans that ended at or after that reading,
        `last` the newest that many (none when <= 0)."""
        recs = list(self._spans)
        if last is not None:
            recs = recs[-int(last):] if int(last) > 0 else []
        return [{"id": sid, "parent": parent, "tid": tid, "name": name,
                 "start": t0, "end": t1, "ids": dict(ids or ())}
                for sid, parent, tid, name, t0, t1, ids in recs
                if since is None or t1 >= since]

    def span_stats(self):
        closed = self._n_spans
        return {"closed": closed, "capacity": self._spans.maxlen,
                "dropped": max(0, closed - self._spans.maxlen)}

    def tail(self, n=None):
        """The newest `n` events (all when n is None, none when
        n <= 0), oldest first, as JSON-ready dicts."""
        with self._lock:
            evs = list(self._ring)
        if n is not None:
            # a plain [-n:] would invert n=0 into "everything"
            evs = evs[-int(n):] if int(n) > 0 else []
        return [dict({"ts": round(ts, 6), "tid": tid, "kind": kind},
                     **(data or {}))
                for ts, tid, kind, data in evs]

    def clear(self):
        with self._lock:
            self._ring.clear()
            self._seq = 0
            self._dropped = 0
            self._spans.clear()
            self._spans_closed = itertools.count(1)
            self._n_spans = 0

    def stats(self):
        with self._lock:
            return {"events": self._seq, "dropped": self._dropped,
                    "capacity": self._ring.maxlen,
                    "size": len(self._ring)}


recorder = FlightRecorder()


def record(kind, **data):
    """Append one structured event to the process-wide flight ring."""
    recorder.record(kind, **data)


def tail(n=None):
    return recorder.tail(n)


def sync_stats():
    recorder.sync_stats()


# ---------------------------------------------------------------------------
# Program spans
# ---------------------------------------------------------------------------

_span_ids = itertools.count(1)
_span_tls = threading.local()

# The host's own accounting of a wait (Linux; a file this host does
# not have gives no id). Module names, so that a test can point them
# elsewhere
_SCHEDSTAT = "/proc/thread-self/schedstat"
_PRESSURE = "/proc/pressure/cpu"
_pressure_fd = False    # not looked for yet; None where the host has none


def _open(path):
    try:
        return os.open(path, os.O_RDONLY)
    except OSError:
        return None


class _ThreadSched:
    """One thread's `schedstat`, kept open for the thread's life
    (`thread-self` is resolved when the file is opened)."""

    __slots__ = ("fd",)

    def __init__(self):
        self.fd = _open(_SCHEDSTAT)

    def __del__(self):
        if self.fd is not None:
            os.close(self.fd)


def _host_reading():
    """{id: microseconds so far} of the host's accounting a wait span
    keeps the growth of, each where the host has it: `runq_us`, the
    calling thread's time runnable without a CPU (`schedstat`'s second
    field), and `pressure_us`, the time in which some runnable task of
    the host had none (the `some` line's total of `/proc/pressure/cpu`).
    Two small reads of files kept open; empty outside Linux and where
    the host keeps neither; never raises."""
    global _pressure_fd
    if not sys.platform.startswith("linux"):
        return {}
    if _pressure_fd is False:
        _pressure_fd = _open(_PRESSURE)
    try:
        sched = _span_tls.sched
    except AttributeError:
        sched = _span_tls.sched = _ThreadSched()
    out = {}
    try:
        if sched.fd is not None:
            out["runq_us"] = int(
                os.pread(sched.fd, 128, 0).split()[1]) // 1000
        if _pressure_fd is not None:
            data = os.pread(_pressure_fd, 256, 0)
            i = data.index(b"total=") + 6
            out["pressure_us"] = int(data[i:data.index(b"\n", i)])
    except (OSError, ValueError, IndexError):
        pass
    return out


class Span:
    """One program span: `with span(...)`, or begin()/end() where a
    `with` does not fit. Its parent is the innermost span open on the
    thread when it began. end() on another thread, or before a span
    opened later on the same thread has ended, still closes the ring
    record; only the annotation of such a span is not to be relied
    on (the profiler writes it where and when end() ran). A span made
    with `wait=True` (wait_span()) reads the host's accounting before
    its clock starts and after it stops, and its ring record carries
    the growth beside its ids."""

    __slots__ = ("name", "ids", "sid", "parent", "tid", "t0", "_ann",
                 "_stack", "_host")

    def __init__(self, name, ids, wait=False):
        self.name = SPAN_PREFIX + name
        self.ids = ids
        self.t0 = None
        # a wait's reading of the host's accounting at begin()
        self._host = {} if wait else None

    def begin(self):
        try:
            stack = _span_tls.stack
        except AttributeError:
            stack = _span_tls.stack = []
        self.parent = stack[-1].sid if stack else 0
        self.sid = next(_span_ids)
        self.tid = threading.get_ident()
        self._stack = stack
        stack.append(self)
        self._ann = TraceAnnotation(self.name, **self.ids)
        self._ann.__enter__()
        if self._host is not None:
            self._host = _host_reading()
        self.t0 = time.perf_counter()
        return self

    def end(self):
        if self.t0 is None:
            return
        t1 = time.perf_counter()
        ids = self.ids
        if self._host:
            now = _host_reading()
            ids = dict(ids, **{k: now[k] - v for k, v
                               in self._host.items() if k in now})
        self._ann.__exit__(None, None, None)
        stack = self._stack
        if stack and stack[-1] is self:
            stack.pop()
        else:
            try:
                stack.remove(self)
            except ValueError:
                pass
        recorder.record_span((self.sid, self.parent, self.tid, self.name,
                              self.t0, t1, ids or None))
        self.t0 = None

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()
        return False


class _NoSpan:
    """What span() hands out while the recorder is off."""

    __slots__ = ()
    t0 = None

    def begin(self):
        return self

    def end(self):
        pass

    __enter__ = begin

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


def span(name, **ids):
    """A program span `paddle_tpu/<name>`, name being `<layer>/<what>`
    with layer one of compile, train, serve, io, comm. `ids`
    say whose work it is: `step=` of a train or engine step, `req=` a
    request's trace id, `program=` of a compile; they are kept with
    the ring record and become the annotation's stats. Off, at the
    cost of this call, with PADDLE_FLIGHT_ENABLE=0."""
    if not recorder.enabled:
        return _NO_SPAN
    return Span(name, ids)


def wait_span(name, **ids):
    """span() for a stretch in which the host only waits for the device
    (`serve/decode/wait`, `train/block`): its ring record also carries
    `runq_us` (the waiting thread's time runnable without a CPU) and
    `pressure_us` (time in which some runnable task of the host had
    none), each where this host keeps it (Linux; a sandboxed kernel
    may keep neither). They tell a stall of the host's scheduler from
    one of the runtime or the device."""
    if not recorder.enabled:
        return _NO_SPAN
    return Span(name, ids, wait=True)


def spans(since=None):
    """The closed spans the ring holds (FlightRecorder.spans)."""
    return recorder.spans(since)


def closed_span(name, start, end, **ids):
    """A span whose extent is known only afterwards (a dispatch that
    turned out to retrace): a child of the innermost span open on the
    thread, in the ring alone. An annotation cannot be backdated."""
    if not recorder.enabled:
        return
    stack = getattr(_span_tls, "stack", None)
    recorder.record_span(
        (next(_span_ids), stack[-1].sid if stack else 0,
         threading.get_ident(), SPAN_PREFIX + name, start, end,
         ids or None))


# ---------------------------------------------------------------------------
# In-flight registry (what the watchdog watches)
# ---------------------------------------------------------------------------

_inflight: dict = {}
_inflight_lock = _sanitize.lock("flight.inflight")
_token_seq = itertools.count(1)

# in-flight kind -> the span it is; {name} is begin()'s `name`, left
# out where it is an id of the work (a request, a checkpoint step)
# rather than the name of a program or an operation
_KIND_SPAN = {
    "compile": "compile/{name}",
    "linalg_compile": "compile/linalg:{name}",
    "capture": "compile/capture/{name}",
    "collective": "comm/{name}",
    "linalg": "comm/linalg:{name}",
    "bootstrap": "comm/bootstrap",
    "ckpt_write": "io/ckpt_write",
    "serve_prefill": "serve/prefill",
    "serve_decode": "serve/decode",
    "serve_drain": "serve/drain",
    "serve_failover": "serve/failover",
    "serve_scale_down": "serve/scale_down",
}


def begin(kind, name, **data):
    """Mark this thread entering a potentially-blocking operation.
    Records a `<kind>_begin` flight event, opens the operation's span
    (`_KIND_SPAN`; `data` are its ids) and registers the op so the
    watchdog can see it wedge. Returns a token for end(); None when
    the recorder is disabled (end(None) is a no-op)."""
    if not recorder.enabled:
        return None
    recorder.record(f"{kind}_begin", name=name, **data)
    token = next(_token_seq)
    sp = Span(_KIND_SPAN.get(kind, "{kind}/{name}").format(
        kind=kind, name=name), data).begin()
    # t0 is wall clock for display; ages/durations measure against
    # the MONOTONIC clock — an NTP step or VM suspend must not fire
    # false watchdog dumps or yield negative dur_us
    entry = dict({"kind": kind, "name": name,
                  "tid": threading.get_ident(),
                  "t0": round(time.time(), 6),
                  "_t0m": time.monotonic(), "_span": sp}, **data)
    with _inflight_lock:
        _inflight[token] = entry
    return token


def end(token):
    """Complete the operation begin() registered: drops it from the
    in-flight table, closes its span and records the `<kind>_end`
    event with its duration."""
    if token is None:
        return
    with _inflight_lock:
        entry = _inflight.pop(token, None)
    if entry is not None:
        entry["_span"].end()
        recorder.record(
            f"{entry['kind']}_end", name=entry["name"],
            dur_us=int((time.monotonic() - entry["_t0m"]) * 1e6))


@contextlib.contextmanager
def in_flight(kind, name, **data):
    token = begin(kind, name, **data)
    try:
        yield
    finally:
        end(token)


def inflight_snapshot(now=None):
    """Current in-flight ops with their ages — what a hung rank was
    doing, straight from the registry the hooks maintain. `now` is a
    time.monotonic() reading (the age clock)."""
    now = time.monotonic() if now is None else now
    with _inflight_lock:
        entries = list(_inflight.values())
    return [_with_age(e, now) for e in entries]


def _with_age(entry, now):
    """An in-flight entry as dumps show it: its age in place of the
    registry's own fields."""
    e = {k: v for k, v in entry.items() if not k.startswith("_")}
    e["age_s"] = round(now - entry["_t0m"], 3)
    return e


# ---------------------------------------------------------------------------
# Dump bundles
# ---------------------------------------------------------------------------

_dump_seq = itertools.count(1)


def _thread_stacks():
    """Formatted stacks of EVERY live thread (the py-spy-style view a
    hang dump needs: the stalled collective's thread plus whoever it
    is waiting on)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, frame in sys._current_frames().items():
        out.append({"tid": tid, "name": names.get(tid, "?"),
                    "stack": traceback.format_stack(frame)})
    return out


def _env_info():
    pfx = ("PADDLE_", "FLAGS_", "JAX_", "XLA_", "GLOG_", "TPU_")
    return {k: os.environ[k] for k in sorted(os.environ)
            if k.startswith(pfx)}


def _device_info():
    if not _jax_backends_live():
        # evidence-gathering must not MUTATE backend state: a dump
        # fired mid-rendezvous (watchdog thread) would otherwise
        # initialize a single-process backend under the main thread's
        # jax.distributed.initialize
        return {"uninitialized": True}
    try:
        import jax

        return {"backend": jax.default_backend(),
                "process_index": jax.process_index(),
                "process_count": jax.process_count(),
                "local_device_count": jax.local_device_count(),
                "device_count": jax.device_count()}
    except Exception as e:  # backend may be unusable mid-crash
        return {"error": f"{type(e).__name__}: {e}"}


def _jit_cache_info():
    try:
        from .. import jit as _jit

        return _jit.cache_report()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def _memory_section(reason, full=None, jit_report=None):
    """Memory evidence for a dump bundle: device stats + per-program
    footprints in EVERY bundle (cheap reads); the live-array census
    joins for OOM and operator-requested (sigusr1) dumps, where "what
    is holding HBM" is the question being asked — `full` overrides
    the reason-based default either way. `jit_report` reuses the
    cache_report() the bundle already computed for its jit_caches
    key instead of walking the live compilers a second time.
    Evidence gathering must not initialize a backend mid-rendezvous
    (see _device_info)."""
    if not _jax_backends_live():
        return {"uninitialized": True}
    try:
        from . import memory as _memory

        if full is None:
            full = reason in ("oom", "sigusr1")
        return _memory.memory_section(census=full,
                                      jit_report=jit_report)
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def _sanitize_section():
    try:
        return _sanitize.describe()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def _numerics_section():
    try:
        from . import numerics

        return numerics.describe()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def _server_section():
    """monitor.server.describe() with a total fallback — the dump
    path (which also runs from the excepthook) must survive a
    half-imported or torn-down server module."""
    try:
        from . import server as _server_mod

        return _server_mod.describe()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def _alerts_section():
    """monitor.alerts.describe() with a total fallback — a dump taken
    before the alerts module finished importing (env autostart runs
    at import time) must still write."""
    try:
        from . import alerts as _alerts_mod

        return _alerts_mod.describe()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def write_dump(reason, extra=None, path=None, full_memory=None):
    """Write one self-contained JSON forensics bundle and return its
    path. Schema (DUMP_SCHEMA = "paddle_tpu.flight/1"):

        schema/reason/ts/rank/world_size/pid/host/argv — identity
        env          — PADDLE_/FLAGS_/JAX_/XLA_/GLOG_/TPU_ vars
        device       — jax backend + process/device counts
        in_flight    — ops currently inside begin()/end() with ages
        threads      — formatted stacks of every live thread
        flight_tail  — newest PADDLE_FLIGHT_DUMP_EVENTS ring events
        span_tail    — as many of the newest closed program spans
        telemetry    — monitor.telemetry_snapshot() (full registry)
        jit_caches   — per-function compiled-program cache keys
        memory       — device stats + per-program footprints (+ the
          live-array census for oom/sigusr1 reasons; `full_memory`
          forces it on/off for custom reasons — oom_observer passes
          True so a renamed OOM bundle keeps its census)
        + reason-specific keys from `extra` (e.g. "exception",
          "stuck")

    The file lands in dump_dir() as
    <reason>_rank<r>_pid<p>_<n>.json (atomic tmp+rename), counted
    under flight/dumps_written, echoed at VLOG(0)."""
    ts = time.time()
    caches = _jit_cache_info()
    n_tail = _env_int("PADDLE_FLIGHT_DUMP_EVENTS", 256)
    payload = {
        "schema": DUMP_SCHEMA,
        "reason": reason,
        "ts": round(ts, 3),
        "rank": _rank(),
        "world_size": _world_size(),
        "pid": os.getpid(),
        "host": socket.gethostname(),
        "argv": list(sys.argv),
        "env": _env_info(),
        "device": _device_info(),
        "in_flight": inflight_snapshot(),
        "threads": _thread_stacks(),
        "flight_tail": recorder.tail(n_tail),
        # the newest closed program spans (perf_counter seconds): what
        # the host was inside of, and for how long, before the incident
        "span_tail": recorder.spans(last=n_tail),
        "jit_caches": caches,
        "memory": _memory_section(
            reason, full=full_memory,
            jit_report=caches if isinstance(caches, list) else None),
        # sanitizer state (ISSUE 10): which families were armed and
        # what they tracked/found — sanitize_arm/sanitize_finding
        # events ride the flight_tail, this is the summary
        "sanitize": _sanitize_section(),
        # numerics-probe state (ISSUE 17): freshest per-tensor
        # absmax/absmin/nonfinite stats — an overflow in this bundle
        # names the offending tensor, not just the skipped step
        "numerics": _numerics_section(),
        # live introspection plane (ISSUE 18): whether a debug server
        # was armed and on which port — a post-mortem can tell
        # whether /profilez etc. were scrapeable before the crash
        "server": _server_section(),
        # SLO alert engine (ISSUE 20): which rules were armed and
        # their pending/firing/resolved states at dump time — a
        # post-mortem can tell whether the SLOs were already burning
        # before the crash
        "alerts": _alerts_section(),
    }
    try:
        from . import telemetry_snapshot

        payload["telemetry"] = telemetry_snapshot()
    except Exception as e:
        payload["telemetry"] = {"error": f"{type(e).__name__}: {e}"}
    if extra:
        payload.update(extra)
    if path is None:
        d = dump_dir()
        os.makedirs(d, exist_ok=True)
        path = os.path.join(
            d, f"{reason}_rank{_rank()}_pid{os.getpid()}_"
               f"{next(_dump_seq)}.json")
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    os.replace(tmp, path)
    _cmon.stat_add("flight/dumps_written", 1)
    recorder.record("dump", reason=reason, path=path)
    try:
        _cmon.VLOG(0, f"flight: wrote {reason} dump -> {path}")
    except Exception:
        # broken/closed stderr must not make a dump that IS on disk
        # look failed (the watchdog would re-dump it every poll)
        pass
    return path


# ---------------------------------------------------------------------------
# Incident hooks (watchdog checkpoint-then-abort)
# ---------------------------------------------------------------------------

# callables fired with (reason) after an incident dump lands — the
# elastic CheckpointManager registers its emergency_save here so a
# hung collective leaves a RESUMABLE snapshot next to the bundle, not
# just an autopsy (ROADMAP item 4 "checkpoint-then-abort")
_incident_hooks: list = []


def add_incident_hook(fn):
    """Register fn(reason) to run after a watchdog incident dump.
    Hooks must be best-effort: exceptions are counted under
    flight/incident_hook/errors and never reach the watchdog loop."""
    if fn not in _incident_hooks:
        _incident_hooks.append(fn)
    return fn


def remove_incident_hook(fn):
    try:
        _incident_hooks.remove(fn)
    except ValueError:
        pass


def _run_incident_hooks(reason):
    for fn in list(_incident_hooks):
        try:
            fn(reason)
        except Exception:
            _cmon.stat_add("flight/incident_hook/errors", 1)


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------

class Watchdog:
    """Background thread that turns a silent hang into evidence.

    Scans the in-flight registry every `poll_s`; any op older than
    `timeout_s` (PADDLE_WATCHDOG_TIMEOUT_S, default 300 — generous
    enough to sit out a first XLA compile) triggers ONE dump naming
    every newly-stuck op. Each stuck op is reported once — a wedged
    collective doesn't re-dump at every poll, but a SECOND op wedging
    later still gets its own bundle."""

    def __init__(self, timeout_s=None, poll_s=None):
        if timeout_s is None:
            timeout_s = _env_float("PADDLE_WATCHDOG_TIMEOUT_S", 300.0)
        self.timeout_s = float(timeout_s)
        if poll_s is None:
            poll_s = _env_float("PADDLE_WATCHDOG_POLL_S", 0.0) \
                or max(0.05, min(self.timeout_s / 4.0, 10.0))
        self.poll_s = float(poll_s)
        self.fired = 0
        self._reported: set = set()   # dumped successfully
        self._noted: set = set()      # ring event recorded
        self._stop = threading.Event()
        self._thread = None

    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="paddle-flight-watchdog",
            daemon=True)
        self._thread.start()
        return self

    def stop(self):
        """Returns False when the thread did not exit within 5s (e.g.
        wedged inside write_dump on a hung filesystem) — it is NOT
        forgotten then (running() stays truthful); once it unblocks,
        the set stop event makes it exit without another scan."""
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
            if t.is_alive():
                _cmon.stat_add("flight/watchdog/errors", 1)
                try:
                    _cmon.VLOG(0, "flight: watchdog thread did not "
                                  "stop within 5s (blocked dump?)")
                except Exception:
                    pass
                return False
        self._thread = None
        return True

    def running(self):
        return self._thread is not None and self._thread.is_alive()

    def _loop(self):
        while not self._stop.wait(self.poll_s):
            try:
                self.check()
            except Exception:
                # the watchdog must NEVER take the training process
                # down — count and keep polling
                _cmon.stat_add("flight/watchdog/errors", 1)

    def check(self, now=None):
        """One scan; returns the dump path when it fired (tests call
        this directly). `now` is a time.monotonic() reading — ages
        ride the monotonic clock so wall-clock steps can't fake (or
        mask) a hang."""
        now = time.monotonic() if now is None else now
        with _inflight_lock:
            items = list(_inflight.items())
        live = {tok for tok, _ in items}
        self._reported &= live  # forget ops that completed
        self._noted &= live
        stuck = [(tok, e) for tok, e in items
                 if now - e["_t0m"] > self.timeout_s
                 and tok not in self._reported]
        if not stuck:
            return None
        detail = [_with_age(e, now) for _, e in stuck]
        # ring event once per stuck op (recorded BEFORE the dump so
        # its tail shows it) — NOT once per retry: a persistently
        # failing dump would otherwise flood the ring with watchdog
        # entries and evict the pre-hang evidence
        fresh = {tok for tok, _ in stuck} - self._noted
        if fresh:
            recorder.record("watchdog",
                            stuck=[e["name"] for _, e in stuck],
                            timeout_s=self.timeout_s)
            self._noted |= fresh
        path = write_dump(
            "watchdog",
            extra={"stuck": detail, "timeout_s": self.timeout_s})
        # mark reported only once the dump is ON DISK — a failed write
        # (unwritable dir, full disk) raises past here into _loop's
        # error counter and the next poll retries, instead of the
        # evidence being suppressed forever
        self._reported |= {tok for tok, _ in stuck}
        self.fired += 1
        _cmon.stat_add("flight/watchdog/fires", 1)
        # checkpoint-then-abort: incident hooks run AFTER the dump is
        # durable (the bundle is cheap and certain; a checkpoint may
        # take seconds and can itself wedge — its ckpt_write span
        # would then show in the NEXT dump)
        _run_incident_hooks("watchdog")
        if _env_on("PADDLE_WATCHDOG_ABORT", default=False):
            # elastic relaunch contract: with evidence + checkpoint on
            # disk, kill the wedged rank so the supervisor restarts
            # the job instead of burning the reservation on a hang
            recorder.record("watchdog_abort")
            try:
                _cmon.VLOG(0, "flight: watchdog aborting process "
                              "(PADDLE_WATCHDOG_ABORT=1)")
            except Exception:
                pass
            os.kill(os.getpid(), signal.SIGABRT)
        return path


_watchdog = None
_watchdog_lock = _sanitize.lock("flight.watchdog")


def get_watchdog():
    return _watchdog


def start_watchdog(timeout_s=None, poll_s=None):
    """Start (or return) the process-wide watchdog. Explicit args
    restart it with the new settings."""
    global _watchdog
    with _watchdog_lock:
        if _watchdog is not None:
            if timeout_s is None and poll_s is None \
                    and _watchdog.running():
                return _watchdog
            _watchdog.stop()
        _watchdog = Watchdog(timeout_s, poll_s).start()
        return _watchdog


def stop_watchdog():
    global _watchdog
    with _watchdog_lock:
        wd, _watchdog = _watchdog, None
    if wd is not None:
        wd.stop()


# ---------------------------------------------------------------------------
# Crash bundles (excepthook / context manager / SIGUSR1)
# ---------------------------------------------------------------------------

def _format_exception(etype, value, tb):
    return {"type": getattr(etype, "__name__", str(etype)),
            "message": str(value),
            "traceback": traceback.format_exception(etype, value, tb)}


def _crash_dump(etype, value, tb):
    # memory.oom_observer may already have bundled THIS exception
    # (with the census taken while the offending arrays were still
    # live) — the excepthook must not shadow it with a second dump
    if getattr(value, "_paddle_flight_dumped", False):
        return None
    recorder.record("exception",
                    type=getattr(etype, "__name__", str(etype)),
                    message=str(value)[:300])
    reason = "crash"
    try:
        from . import memory as _memory

        if _memory.is_oom_error(value):
            # RESOURCE_EXHAUSTED gets its own reason (an operator
            # greps for oom_rank*.json) and the full census in its
            # memory section (_memory_section keys off the reason)
            reason = "oom"
    except Exception:
        pass
    return write_dump(
        reason, extra={"exception": _format_exception(etype, value,
                                                      tb)})


_orig_excepthook = None
_orig_threading_hook = None
_excepthook_installed = False
_excepthook_running = False


def _flight_excepthook(etype, value, tb):
    global _excepthook_running
    if _excepthook_running:
        # re-entered through a hook cycle (a third-party hook chained
        # back to us) — break it, print the real traceback once
        sys.__excepthook__(etype, value, tb)
        return
    _excepthook_running = True
    try:
        if _excepthook_installed:
            try:
                _crash_dump(etype, value, tb)
            except Exception:
                pass  # forensics must not mask the original crash
        (_orig_excepthook or sys.__excepthook__)(etype, value, tb)
    finally:
        _excepthook_running = False


def _flight_threading_excepthook(args):
    """threading.excepthook leg: an unhandled exception on a WORKER
    thread (dataloader producer, user prefetch thread) never reaches
    sys.excepthook — without this, a run that degrades after a thread
    death leaves no bundle."""
    if _excepthook_installed \
            and args.exc_type is not SystemExit:
        try:
            _crash_dump(args.exc_type, args.exc_value,
                        args.exc_traceback)
        except Exception:
            pass
    (_orig_threading_hook or threading.__excepthook__)(args)


def install_excepthook():
    """Chain a crash-bundle writer in front of sys.excepthook AND
    threading.excepthook: any unhandled exception — main or worker
    thread — leaves one inspectable JSON bundle before the normal
    traceback prints. Idempotent — and guarded by a flag, NOT by
    `sys.excepthook is ours`: re-installing after a third-party hook
    wrapped us would capture that wrapper as our `orig` and
    crash-time dispatch would cycle forever."""
    global _orig_excepthook, _orig_threading_hook, \
        _excepthook_installed
    if _excepthook_installed:
        return
    if _orig_excepthook is None:
        _orig_excepthook = sys.excepthook
        sys.excepthook = _flight_excepthook
    if _orig_threading_hook is None:
        _orig_threading_hook = threading.excepthook
        threading.excepthook = _flight_threading_excepthook
    # else: a prior uninstall-while-wrapped left our link inside a
    # third-party chain with the app's original retained — re-enable
    # via the flag alone; re-capturing the hook here would capture
    # the wrapper (dispatch cycle) and drop the original
    _excepthook_installed = True


def uninstall_excepthook():
    global _orig_excepthook, _orig_threading_hook, \
        _excepthook_installed
    if not _excepthook_installed:
        return
    if sys.excepthook is _flight_excepthook:
        sys.excepthook = _orig_excepthook or sys.__excepthook__
        _orig_excepthook = None
    if threading.excepthook is _flight_threading_excepthook:
        threading.excepthook = _orig_threading_hook \
            or threading.__excepthook__
        _orig_threading_hook = None
    # else: someone wrapped us — leave the chain intact (our link
    # becomes a pass-through via the flag) and keep the originals so
    # the chains still terminate correctly
    _excepthook_installed = False


@contextlib.contextmanager
def dump_on_crash():
    """Context-manager flavor of the excepthook for code the top-level
    hook never sees (worker threads, callers that catch and exit):
    writes the crash bundle, then re-raises."""
    try:
        yield
    except Exception:
        try:
            _crash_dump(*sys.exc_info())
        except Exception:
            pass
        raise


_orig_sig_handler = None
_orig_sig_signum = None
_sig_installed = None
_sig_running = False


def _signal_handler(signum, frame):
    # NEVER dump inline: the handler runs between bytecodes on the
    # main thread, possibly while the interrupted frame holds
    # recorder._lock / _inflight_lock / a StatRegistry lock — none
    # reentrant, so write_dump() here could wedge the very rank the
    # live dump is inspecting. A spawned thread queues behind the
    # lock instead.
    global _sig_running
    if _sig_running:
        return  # handler-chain cycle — break it
    _sig_running = True
    try:
        if _sig_installed == signum:  # armed for THIS signal
            def _dump():
                try:
                    write_dump("sigusr1")
                except Exception:
                    pass

            threading.Thread(target=_dump,
                             name="paddle-flight-sigusr1",
                             daemon=True).start()
        # chain like the excepthook does: auto-arm must not eat an
        # application's own SIGUSR1 handler (e.g. the cluster
        # checkpoint-on-preemption trigger); the retained original
        # belongs to one specific signal
        if signum == _orig_sig_signum and callable(_orig_sig_handler):
            _orig_sig_handler(signum, frame)
    finally:
        _sig_running = False


def install_signal_handler(signum=None):
    """Wire SIGUSR1 (or `signum`) to a live dump — `kill -USR1 <pid>`
    inspects a running rank without stopping it. A previously
    installed application handler is chained (called after the dump
    thread is spawned), and uninstall_signal_handler restores it.
    Idempotent via an installed flag (NOT handler identity — see
    install_excepthook). ONE live-dump signal at a time: asking for a
    second signal while another is armed (or while a dormant chain on
    another signal still routes through us) returns False rather than
    claiming success. Also returns False where installing is
    impossible (no SIGUSR1 on the platform, or not the main
    thread)."""
    global _orig_sig_handler, _orig_sig_signum, _sig_installed
    if signum is None:
        signum = getattr(signal, "SIGUSR1", None)
        if signum is None:
            return False
    if _sig_installed is not None:
        return signum == _sig_installed
    if _orig_sig_handler is not None:
        if signum != _orig_sig_signum:
            # a dormant (uninstalled-while-wrapped) chain on another
            # signal still routes through us; rewiring for a second
            # signal would orphan that chain's original handler
            return False
        # prior uninstall-while-wrapped ON THIS SIGNAL: our link still
        # sits inside a third-party chain — re-enable via the flag
        # alone (see install_excepthook)
        _sig_installed = signum
        return True
    try:
        prev = signal.signal(signum, _signal_handler)
    except (ValueError, OSError):
        return False
    if prev is not _signal_handler:
        _orig_sig_handler = prev
        _orig_sig_signum = signum
    _sig_installed = signum
    return True


def uninstall_signal_handler():
    global _orig_sig_handler, _orig_sig_signum, _sig_installed
    if _sig_installed is None:
        return
    try:
        if signal.getsignal(_sig_installed) is _signal_handler:
            signal.signal(_sig_installed,
                          _orig_sig_handler or signal.SIG_DFL)
            _orig_sig_handler = None
            _orig_sig_signum = None
        # else: wrapped by a later handler — leave the chain intact
        # (the cleared _sig_installed makes our link dump-free) and
        # keep _orig_sig_handler so the chain still terminates
    except (ValueError, OSError):
        pass
    _sig_installed = None


# ---------------------------------------------------------------------------
# Arming
# ---------------------------------------------------------------------------

def arm(watchdog=True, excepthook=True, usr1=True, timeout_s=None,
        poll_s=None):
    """Switch the full forensics layer on (recorder is always on
    unless PADDLE_FLIGHT_ENABLE=0). Returns the watchdog (or None).
    With the recorder disabled the watchdog is skipped too — begin()
    registers nothing, so the thread would poll an always-empty table
    forever; crash/SIGUSR1 dumps still work (stacks + telemetry, just
    no ring tail)."""
    if excepthook:
        install_excepthook()
    if usr1:
        install_signal_handler()
    if watchdog and recorder.enabled:
        return start_watchdog(timeout_s, poll_s)
    return None


def maybe_auto_arm(where=""):
    """Env-gated arm() called from hapi.Model.fit and
    distributed.init_parallel_env: PADDLE_FLIGHT_AUTOARM set non-falsy
    forces on, falsy forces off; unset arms only distributed runs
    (PADDLE_TRAINERS_NUM > 1) — single-host notebooks keep their
    excepthook untouched unless they opt in."""
    if not _env_on("PADDLE_FLIGHT_AUTOARM",
                   default=_world_size() > 1):
        return None
    recorder.record("auto_arm", where=where)
    return arm()


# the PADDLE_SANITIZE env autostart arms from inside this module's own
# `from . import sanitize` (before the recorder existed) — replay any
# events it buffered so the sanitize_arm event reaches the ring
_sanitize.flush_flight_events()
