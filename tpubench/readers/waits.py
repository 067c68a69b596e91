"""Readers that take the wait for the device apart, on both clocks.

The program meets the runtime in three spans (PERF.md section 3): the
enqueue of a dispatch (`serve/decode/enqueue` > `serve/decode/put`,
`train/enqueue`), host work done beside the device (`serve/decode/ahead`) and
the wait alone (`serve/decode/wait`, `train/block`). The wait spans are made
with `flight.wait_span`: their ring records carry what the host's scheduler
did to the waiting thread (`runq_us`, `pressure_us`, each where the host
keeps it).

* On the device's clock (the trace's host plane against `XLA Ops` and `XLA
  Modules`): the chip's idle time from the opening of an enqueue to its next
  busy instant (launch) and inside a wait (wake), together the runtime's
  round trip (`idle_roundtrip_share`); what is left of the idle time inside
  the program's spans is the host's own code. The two clocks agree only to
  within an interval that every traced run bounds (`clock_skew`): a program
  cannot start before its enqueue opened (nor before `serve/decode/put`, the
  transfer of its inputs, closed), and a wait cannot close before its program
  ended. The round trip is the same wherever in that interval the
  device's times are put, and wrong outside it; HOW it divides into launch
  and wake moves by the interval's width, about the size of either part, so
  the two are not metrics: the run's log gives the interval and both gaps'
  medians as the trace shows them.
* On the host's clock (the ring, the whole window): one row a slow step
  (`stalls`), by the rule of `program.slow_step_excess_ms`, with the wait
  spans' accounting; in a traced run the rows of the traced steps also carry
  the launch gap, the device program's time and the wake gap.
  `slow_step_starved_ms` and `slow_step_device_ms` are sums over those rows.

A program without wait spans (the parent of the PR that added them), a run on
the CPU or one without a trace gives the trace readers nothing to read: each
returns None and raises nothing.
"""
from __future__ import annotations

import bisect
import statistics

from .. import core
from ..xplane import WINDOW_SPAN, measure, subtract, union
from . import program

PREFIX = program.PREFIX
STARVED = ("runq_us", "pressure_us")
# how far the two clocks may differ: a program is looked for this far before
# its enqueue opened, and this far after its wait closed (xplane.py says
# "about a millisecond"; the intervals measured on the chip reached 2.25 ms;
# a serial step's programs start 9 ms apart or more, one that runs ahead
# opens its enqueue 6 ms after the last program started)
SLACK_S = 3e-3


def has_waits():
    """Does the program make wait spans at all?"""
    from paddle_tpu.monitor import flight

    return hasattr(flight, "wait_span")


def intersect(a, b):
    """Parts of the merged intervals `a` that the merged `b` covers."""
    return subtract(a, subtract(a, b))


# -- the trace's host plane ---------------------------------------------------

def host_plane(path):
    """(driver, runtime) of a trace's `/host:CPU` plane. driver: the
    `paddle_tpu/` events of the thread that drove the run (the line that
    holds the benchmark's trace-window span, else every line), as (name,
    start_s, end_s, step id or None), by start. runtime: the events of every
    other line (the runtime's own threads), as (line, name, start_s, end_s),
    by start."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            driver, mine, other = False, [], []
            for e in line.events:
                a = e.start_ns * 1e-9
                b = a + e.duration_ns * 1e-9
                if e.name.startswith(PREFIX):
                    step = None
                    if e.name.endswith("/step"):
                        step = dict(e.stats).get("step")
                    mine.append((e.name, a, b,
                                 None if step is None else int(step)))
                elif e.name == WINDOW_SPAN:
                    driver = True
                else:
                    other.append((line.name, e.name, a, b))
            lines.append((driver, mine, other))
    one = any(driver for driver, _, _ in lines)
    return (sorted((e for driver, mine, _ in lines if driver or not one
                    for e in mine), key=lambda e: e[1]),
            sorted((e for driver, _, other in lines if not driver
                    for e in other), key=lambda e: e[2]))


def _traced(run):
    """(reduced trace, driver events, runtime events) of a run on the chip
    whose program makes wait spans and left its spans in the trace."""
    tr = run.reduced_trace() if run.on_tpu and has_waits() else None
    if tr is None or not tr.window_seconds() or not tr.devices:
        return None
    if not hasattr(run, "_waits_host"):
        run._waits_host = host_plane(run.trace_file)
    driver, runtime = run._waits_host
    return (tr, driver, runtime) if driver else None


def _named(driver, name):
    return [(a, b) for n, a, b, _ in driver if n == PREFIX + name]


# -- idle time: the round trip, the rest ---------------------------------------

def roundtrip_idle(tr, driver, enqueue, wait, shift=0.0):
    """(round trip, inside), in % of the traced window, mean over the chips.
    inside: the chip idle while the host is inside a span of the program, as
    `program.split_idle` takes it. round trip: the part of it from the
    opening of an `enqueue` span to the chip's next busy instant, whatever
    span the host has moved on to (launch), or while the host is inside a
    `wait` span (wake); the rest is the host's own code. `shift`: seconds
    added to the device's times first (`clock_skew`), for both alike."""
    inside = union((a, b) for _, a, b, _ in driver)
    opened = sorted(a for a, _ in _named(driver, enqueue))
    waiting = union(_named(driver, wait))
    round_trip = whole = 0.0
    for chip in tr.devices:
        busy = [(a + shift, b + shift) for a, b in tr.busy(chip)]
        gaps = intersect(subtract([tr.window], busy), inside)
        starts = [a for a, _ in gaps]
        launching = []
        for t in opened:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t < gaps[i][1]:
                launching.append((t, gaps[i][1]))
        whole += measure(gaps)
        round_trip += measure(union(launching + intersect(gaps, waiting)))
    scale = 100.0 / (len(tr.devices) * tr.window_seconds())
    return round_trip * scale, whole * scale


def idle_roundtrip_share(run, enqueue, wait, module, after=None):
    """roundtrip_idle() of the run's trace, the device's times put in the
    middle of the clocks' interval first: any point of it gives the same,
    since the host's own code between a wait's close and the next enqueue's
    opening then lies wholly inside the chip's gap. The log says the
    interval, the rest, and `program.split_idle`'s whole on the times as
    they are, which `idle_in_program_share` reports. None where there is
    nothing to read, and where the interval is empty (lo > hi: the spans
    and the programs do not belong together, and no number would mean
    anything)."""
    got = _traced(run)
    if got is None or not _named(got[1], wait):
        return None
    tr, driver, _ = got
    skew = clock_skew(tr, driver, enqueue, wait, module, after)
    if skew is None:
        return None
    said = ("the two clocks: a device time is on the host's clock after "
            "adding {lo_ms:.3f} to {hi_ms:.3f} ms (width {width_ms:.3f}) "
            "over {enqueues} enqueues and {waits} waits; as the trace shows "
            "them, launch gap p50 {launch_ms_p50:.3f} ms, wake gap p50 "
            "{wake_ms_p50:.3f} ms; ".format(**skew))
    if skew["width_ms"] < 0:
        core.say(said + "an empty interval: no round trip is read")
        return None
    round_trip, whole = roundtrip_idle(
        tr, driver, enqueue, wait, (skew["lo_ms"] + skew["hi_ms"]) / 2e3)
    asis = program.split_idle(tr, union((a, b) for _, a, b, _ in driver))[0]
    core.say(said + f"with the device in the middle, idle inside the "
             f"program's spans {whole:.3f} % = round trip {round_trip:.3f} "
             f"+ the host's own code {whole - round_trip:.3f} (as "
             f"idle_in_program_share takes the times: {asis:.3f})")
    return round_trip


# -- the device's programs against the host's spans ----------------------------

def _programs(tr, module):
    """[(start, end)] by start, and the median seconds, of the step's device
    program on the first chip: of the programs whose name starts with
    `module`, wholly inside the window, the one (name and hash) with the most
    device seconds. Not the most frequent, which `decode_roofline.*` takes:
    on four chips a small program that shards a batch runs twice a train
    step (`jit__multi_slice`); in the one-chip cells the two agree."""
    lo, hi = tr.window
    by_name = {}
    for name, a, b in tr.devices[min(tr.devices)]["modules"]:
        if name.startswith(module) and a >= lo and b <= hi:
            by_name.setdefault(name, []).append((a, b))
    if not by_name:
        return [], None
    evs = sorted(max(sorted(by_name.items()),
                     key=lambda kv: sum(b - a for a, b in kv[1]))[1])
    return evs, statistics.median(b - a for a, b in evs)


def _launched(progs, t):
    """The program an enqueue opened at `t` started: the first to start no
    earlier than SLACK_S before it."""
    i = bisect.bisect_left(progs, (t - SLACK_S,))
    return progs[i] if i < len(progs) else None


def _awaited(progs, ends, t):
    """The program a wait closed at `t` waited for: the last to end no later
    than SLACK_S after it (`ends`: the programs' ends, sorted)."""
    i = bisect.bisect_right(ends, t + SLACK_S) - 1
    return progs[i] if i >= 0 else None


def clock_skew(tr, driver, enqueue, wait, module, after=None):
    """The interval, in ms, of what has to be added to a device time of this
    trace to put it on the host's clock: at least minus the smallest gap from
    an enqueue's opening to its program's start (from the close of the
    `after` span inside the enqueue, where the program has one that ends
    before it calls the program: the inputs' transfer), at most the smallest
    gap from a program's end to its wait's close. Also the medians of the
    gaps from the enqueue's opening and to the wait's close as the trace
    shows them. None where the trace holds no such program."""
    progs, _ = _programs(tr, module)
    if not progs:
        return None
    ends = sorted(b for _, b in progs)

    def gaps(times):
        return [p[0] - t for t in times
                for p in [_launched(progs, t)] if p is not None]

    launch = gaps(a for a, _ in _named(driver, enqueue))
    wake = [t - p[1] for _, t in _named(driver, wait)
            for p in [_awaited(progs, ends, t)] if p is not None]
    if not launch or not wake:
        return None
    called = gaps(b for _, b in _named(driver, after)) if after else ()
    lo, hi = -min(called or launch), min(wake)
    return {"lo_ms": 1e3 * lo, "hi_ms": 1e3 * hi,
            "width_ms": 1e3 * (hi - lo),
            "enqueues": len(launch), "waits": len(wake),
            "launch_ms_p50": 1e3 * statistics.median(launch),
            "wake_ms_p50": 1e3 * statistics.median(wake)}


# -- one row a slow step -----------------------------------------------------

def stalls(run, root, wait, enqueue=None, module=None, ends="step_end_s"):
    """One dict a slow step of the window, in order; None with fewer than
    three steps. A step and "slow" are `program.slow_step_excess_ms`'s: from
    the start of one `root` span to the start of the next, longer than 1.02 x
    the median, the step in which a traced run started the profiler left out.

    From the ring: `step` (the root's id), `at_s` from the window's opening,
    `step_ms`, `excess_ms` over the median step, `next_step_ms` (with a
    dispatch queued ahead, a late host shortens the next step by as much),
    `excess_by_span_ms` (each descendant name's time over that name's median
    a step, where over 0.05 ms: where the excess fell), and over the step's
    `wait` spans the sums of `runq_us` and `pressure_us` where the records
    carry them, with `starved_us`, the sum over those spans of the larger of
    the two (absent where the records carry neither).

    From the trace, for a step that lies in the traced window of a run on the
    chip (`enqueue` and `module` given): `launch_gap_ms` from an enqueue's
    opening to its program's start, `device_ms` of the programs that ended
    in the step and `device_excess_ms`, what those of them that ran longer
    than 1.02 x the median program took over it,
    `wake_gap_ms` from a program's end to its wait's close (both gaps as the
    trace shows them, `clock_skew` not applied), and `runtime_first`, the
    first event of the runtime's own threads between the end of the step's
    last program and the close of its wait, with `after_ms` from that end,
    its own `ms` and `of`, how many there are: a runtime that is silent for
    most of a long wake gap, or whose first event lasts that long, learned
    late that the program had ended."""
    found = program.steps(run, root)
    if len(found) < 3:
        return None
    starts = [r["start"] for r, _ in found] + [found[-1][0]["end"]]
    whole = [b - a for a, b in zip(starts, starts[1:])]
    usual = statistics.median(whole)
    t_trace = program._profiler_started(run, ends)
    waits = {PREFIX + n for n in wait}
    by_name = [_by_name(kids) for _, kids in found]
    medians = {n: statistics.median(d.get(n, 0.0) for d in by_name)
               for n in set().union(*by_name)}
    traced = _traced_steps(run, root, enqueue, wait, module)
    rows = []
    for i, ((r, kids), a, w) in enumerate(zip(found, starts, whole)):
        if w <= 1.02 * usual \
                or (t_trace is not None and a <= t_trace < a + w):
            continue
        row = {"step": r["ids"].get("step"), "at_s": a - run.window[0],
               "step_ms": 1e3 * w, "excess_ms": 1e3 * (w - usual),
               "next_step_ms": 1e3 * whole[i + 1]
               if i + 1 < len(whole) else None,
               "excess_by_span_ms": {
                   n[len(PREFIX):]: 1e3 * (s - medians[n])
                   for n, s in sorted(by_name[i].items())
                   if s - medians[n] > 5e-5}}
        mine = [k["ids"] for k in kids if k["name"] in waits]
        for key in STARVED:
            if any(key in ids for ids in mine):
                row[key] = sum(ids.get(key, 0) for ids in mine)
        if any(k in row for k in STARVED):
            row["starved_us"] = sum(
                max(ids.get(k, 0) for k in STARVED) for ids in mine)
        row.update(traced(row["step"]))
        rows.append(row)
    return rows


def _by_name(kids):
    out = {}
    for k in kids:
        out[k["name"]] = out.get(k["name"], 0.0) + k["end"] - k["start"]
    return out


def _traced_steps(run, root, enqueue, wait, module):
    """f(step id) -> the trace's part of a stall's row ({} for a step
    outside the traced window, or without a trace)."""
    got = _traced(run) if enqueue and module else None
    progs, usual = _programs(got[0], module) if got else ([], None)
    if not progs:
        return lambda step: {}
    tr, driver, runtime = got
    ends = sorted(b for _, b in progs)
    roots = [(a, step) for n, a, _, step in driver if n == PREFIX + root]
    begin = {step: (a, roots[i + 1][0] if i + 1 < len(roots) else None)
             for i, (a, step) in enumerate(roots)}
    waits = [iv for n in wait for iv in _named(driver, n)]

    def part(step):
        a, b = begin.get(step, (None, None))
        if a is None or b is None or a < tr.window[0] or b > tr.window[1]:
            return {}
        ran = [p for p in progs if a <= p[1] < b]
        out = {"device_ms": 1e3 * sum(q - p for p, q in ran),
               "device_excess_ms": 1e3 * sum(
                   q - p - usual for p, q in ran
                   if q - p > 1.02 * usual)}
        launched = [p[0] - t for t, _ in _named(driver, enqueue)
                    if a <= t < b for p in [_launched(progs, t)]
                    if p is not None]
        closed = [t for _, t in waits if a <= t < b]
        woken = [t - p[1] for t in closed
                 for p in [_awaited(progs, ends, t)] if p is not None]
        if launched:
            out["launch_gap_ms"] = 1e3 * max(launched)
        if woken:
            out["wake_gap_ms"] = 1e3 * max(woken)
        if ran and closed and max(closed) > ran[-1][1]:
            lo, hi = ran[-1][1], max(closed)
            i = bisect.bisect_left(runtime, lo, key=lambda e: e[2])
            j = bisect.bisect_left(runtime, hi, key=lambda e: e[2])
            if i < j:
                line, name, p, q = runtime[i]
                out["runtime_first"] = {"line": line, "name": name,
                                        "after_ms": 1e3 * (p - lo),
                                        "ms": 1e3 * (q - p), "of": j - i}
        return out

    return part


def slow_step_starved_ms(run, root, wait):
    """Over the window's slow steps, the ms their wait spans say some
    runnable task had no CPU: the sum over those spans of the larger of
    `runq_us` and `pressure_us`. Near `slow_step_excess_wait_ms`: the host's
    scheduler; near 0 beside a large one: not the host's CPUs. 0 without a
    slow step. None where no wait span of the window carries either: at a
    program without wait spans (the parent), and on a host that keeps
    neither file, which is every host the benchmark has run on so far
    (PERF.md section 3), so no entry of BENCHMARK.json names this reader
    yet."""
    names = {PREFIX + n for n in wait}
    if not any(i in k["ids"] for _, kids in program.steps(run, root)
               for k in kids if k["name"] in names for i in STARVED):
        return None
    rows = stalls(run, root, wait)
    return None if rows is None else sum(
        r.get("starved_us", 0) for r in rows) / 1e3


def slow_step_device_ms(run, root, wait, enqueue, module):
    """Over the slow steps that lie in the traced window, the ms by which
    the device programs that ended in them (`_programs`: of those whose name
    starts with `module`, the one with the most device seconds, first chip)
    and ran longer than 1.02 x the
    median program exceeded it. 0 in a trace without a slow step; non-zero:
    the chip."""
    got = _traced(run)
    if got is None or not _programs(got[0], module)[0]:
        return None
    rows = stalls(run, root, wait, enqueue, module)
    return None if rows is None else sum(
        r.get("device_excess_ms", 0.0) for r in rows)
