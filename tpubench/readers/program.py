"""Readers over the program's own spans (paddle_tpu.monitor.flight.span) and
the gauges it keeps beside them.

Two sources, one span scheme (`paddle_tpu/<layer>/<what>`, PERF.md section 3):

* the ring, `flight.spans()`: every closed span of the process on
  time.perf_counter(), the clock the run's window is cut with. Per-step
  medians, the slow steps' excess and the set-up's compile seconds come from
  it, over the whole window or the whole set-up, traced run or not.
* the host plane of the run's trace: the same spans as the profiler wrote
  them, on the device's clock. The split of the device's idle time into what
  fell inside the program and what fell outside comes from it, against
  `Trace.busy()`, and needs the chip.

A program without such spans (the parent of the PR that added them) gives
every reader nothing to read: each returns None and raises nothing.
"""
from __future__ import annotations

import fnmatch
import statistics

from ..xplane import WINDOW_SPAN, measure, subtract, union

PREFIX = "paddle_tpu/"


# -- the ring ---------------------------------------------------------------

def ring(run):
    """The program's closed spans as flight.spans() gives them, read once a
    run; None where the program keeps none."""
    if not hasattr(run, "_program_ring"):
        from paddle_tpu.monitor import flight

        read = getattr(flight, "spans", None)
        run._program_ring = read() if read is not None else None
    return run._program_ring or None


def steps(run, root):
    """[(root span, [its descendants])] of the `root` spans (a name under the
    prefix, e.g. "train/step") that began and ended inside the run's window,
    in order."""
    spans = ring(run)
    if not spans or not run.window:
        return []
    lo, hi = run.window
    roots = {s["id"]: (s, []) for s in spans if s["name"] == PREFIX + root
             and s["start"] >= lo and s["end"] <= hi}
    parent = {s["id"]: s["parent"] for s in spans}
    for s in spans:
        p = s["parent"]
        while p and p not in roots:
            p = parent.get(p, 0)
        if p:
            roots[p][1].append(s)
    return sorted(roots.values(), key=lambda r: r[0]["start"])


def _inside(children, names):
    """Seconds inside the children named so (under the prefix)."""
    want = {PREFIX + n for n in names}
    return sum(c["end"] - c["start"] for c in children if c["name"] in want)


def step_ms_p50(run, root, include=None, exclude=None):
    """Median over the window's steps, in ms: of the time inside the
    `include`d descendants of each `root` span, or of the root's own time less
    its `exclude`d descendants."""
    vals = [_inside(kids, include) if include is not None
            else (r["end"] - r["start"]) - _inside(kids, exclude or ())
            for r, kids in steps(run, root)]
    return 1e3 * statistics.median(vals) if vals else None


def slow_step_excess_ms(run, root, wait, part, ends="step_end_s"):
    """Over the whole window, for the steps longer than 1.02 x the median
    step: the milliseconds by which their waiting (`part` "wait": inside the
    `wait` descendants, where the host blocks on the device) or the rest of
    them (`part` "host": every other child, the root's own time and the
    caller's code up to the next step) exceeds the median step's. A step runs
    from the start of its `root` span to the start of the next; the last ends
    with its span. 0 when no step was slow. In a traced run the step during
    which the benchmark started the profiler is left out: that stall is the
    measurement's own."""
    found = steps(run, root)
    if len(found) < 3:
        return None
    starts = [r["start"] for r, _ in found] + [found[-1][0]["end"]]
    t_trace = _profiler_started(run, ends)
    whole = [b - a for a, b in zip(starts, starts[1:])]
    waits = [_inside(kids, wait) for _, kids in found]
    parts = waits if part == "wait" else [w - x for w, x in zip(whole, waits)]
    limit, usual = 1.02 * statistics.median(whole), statistics.median(parts)
    return 1e3 * sum(
        max(0.0, p - usual) for a, b, w, p
        in zip(starts, starts[1:], whole, parts)
        if w > limit and not (t_trace is not None and a <= t_trace < b))


def _profiler_started(run, ends):
    """When a traced run started the profiler (perf_counter): a kind starts it
    right after the first step that ends inside the last `trace_seconds` of
    the window (core.Run.trace_due)."""
    if not run.trace:
        return None
    due = run.seconds - float(run.traffic.get("trace_seconds", 4))
    return next((run.window[0] + e for e in run.samples.get(ends, ())
                 if e >= due), None)


def setup_seconds(run, names):
    """Seconds, from process start to the opening of the window, inside spans
    whose name matches one of the glob patterns `names` (under the prefix);
    nested and repeated spans count once."""
    spans = ring(run)
    if not spans or not run.window:
        return None
    t_open = run.window[0]
    return measure(union(
        (s["start"], min(s["end"], t_open)) for s in spans
        if s["start"] < t_open and any(
            fnmatch.fnmatchcase(s["name"], PREFIX + n) for n in names)))


def gauge(run, names, scale=1.0):
    """The largest, at the close of the window, of the program's gauges that
    match one of the glob patterns `names`; None where it set none."""
    snap = run.counters.get("close") or {}
    vals = [v for k, v in snap.items()
            if any(fnmatch.fnmatchcase(k, n) for n in names)]
    return scale * max(vals) if vals else None


# -- the trace --------------------------------------------------------------

def host_spans(path):
    """Merged [(start_s, end_s)] of the `paddle_tpu/` events on the host
    thread that drove the run: the line of the trace's `/host:CPU` plane that
    holds the benchmark's trace-window span, or every line where none does."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            driver, evs = False, []
            for e in line.events:
                if e.name.startswith(PREFIX):
                    evs.append((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
                elif e.name == WINDOW_SPAN:
                    driver = True
            lines.append((driver, evs))
    chosen = [evs for driver, evs in lines if driver] \
        or [evs for _, evs in lines]
    return union(iv for evs in chosen for iv in evs)


def idle_split(run):
    """(inside, outside): the shares, in % of the traced window, of the time
    the chips were idle while the driving host thread was inside any span of
    the program, and while it was not (the caller's code: a loss fetch, the
    next batch). Mean over the chips, as device.idle_share, so the two sum to
    it. None without a chip, a trace or a span of the program in it."""
    tr = run.reduced_trace() if run.on_tpu else None
    if tr is None or not tr.window_seconds() or not tr.devices:
        return None
    if not hasattr(run, "_program_host"):
        run._program_host = host_spans(run.trace_file)
    inside = run._program_host
    if not inside:
        return None
    return split_idle(tr, inside)


def split_idle(tr, inside):
    """idle_split() of a reduced trace, given the merged host intervals."""
    idle_in = idle_all = 0.0
    for chip in tr.devices:
        gaps = subtract([tr.window], tr.busy(chip))
        idle_all += measure(gaps)
        idle_in += measure(gaps) - measure(subtract(gaps, inside))
    scale = 100.0 / (len(tr.devices) * tr.window_seconds())
    return idle_in * scale, (idle_all - idle_in) * scale


def idle_in_program_share(run):
    s = idle_split(run)
    return None if s is None else s[0]


def idle_outside_program_share(run):
    s = idle_split(run)
    return None if s is None else s[1]
