"""The readers over the spans where the program meets the runtime
(tpubench/readers/waits.py): on synthetic spans and rings, on the recorded
v5e trace plus synthetic host events, and in a toy cell run by the one
command on the CPU. The rings, runs and fixtures are those of
test_tpubench_program_spans.py, which this file leaves as it is."""
import json
import os
import subprocess
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy_tree  # noqa: E402
import test_tpubench_program_spans as base  # noqa: E402

from tpubench import xplane  # noqa: E402
from tpubench.readers import program, waits  # noqa: E402

PROBE, P, fake_run = base.PROBE, base.P, base.fake_run
trace, tree = base.trace, base.tree


class Ring(base.Ring):
    def train_step(self, t, host=None, **kw):
        """base.Ring's steady train step; `host`: the ids its block carries
        as a wait span's record does."""
        end = super().train_step(t, **kw)
        block = next(s for s in reversed(self.spans)
                     if s["name"] == P + "train/block")
        block["ids"].update(host or {})
        return end


def steady_run(n=20, host=None, **stalls):
    """base.steady_run; `host`: f(i) -> the ids step i's block carries."""
    ring, t = Ring(), 100.0
    for i in range(n):
        extra = {k: v[1] for k, v in stalls.items() if v[0] == i}
        t = ring.train_step(t, block=0.290 + extra.get("block", 0.0),
                            prepare=0.002 + extra.get("prepare", 0.0) / 2,
                            n=i, host=host(i) if host else None)
        t += 0.004 + extra.get("gap", 0.0)
    return ring, (100.0, t)


QUIET = {"runq_us": 3, "pressure_us": 0}


@pytest.mark.parametrize("stalled,want", [
    # the waiting thread had no CPU for 78 of the 80 ms
    ({"runq_us": 78000, "pressure_us": 1200}, 78.0),
    # another task of the host was the one that was starved: the larger of
    # the two, not their sum
    ({"runq_us": 5, "pressure_us": 64000}, 64.0),
    # a host that keeps the thread's schedstat alone
    ({"runq_us": 700}, 0.7),
    # the host's scheduler saw nothing: the runtime's or the chip's
    ({"runq_us": 2, "pressure_us": 0}, 0.002),
])
def test_a_planted_stall_lands_in_starved_ms_by_what_its_wait_carries(
        stalled, want):
    ring, window = steady_run(
        block=(7, 0.080), host=lambda i: stalled if i == 7 else QUIET)
    run = fake_run(ring, window)
    assert waits.slow_step_starved_ms(run, "train/step", ["train/block"]) \
        == pytest.approx(want)
    # the same steps as slow_step_excess_ms calls slow
    (row,) = waits.stalls(run, "train/step", ["train/block"])
    assert row["step"] == 7 and row["excess_ms"] == pytest.approx(80.0)
    assert row["excess_by_span_ms"] == {"train/block": pytest.approx(80.0)}
    assert row["runq_us"] == stalled["runq_us"] and row["next_step_ms"] \
        == pytest.approx(row["step_ms"] - 80.0)
    assert not set(row) & {"device_ms", "launch_gap_ms", "wake_gap_ms"}


def test_starved_ms_is_zero_without_a_slow_step_and_none_without_ids():
    ring, window = steady_run(host=lambda i: QUIET)
    assert waits.slow_step_starved_ms(
        fake_run(ring, window), "train/step", ["train/block"]) == 0.0
    # a host that keeps neither file (the chip's), and the parent, whose
    # block is a plain span: nothing to read, not "0 ms starved"
    ring, window = steady_run(block=(7, 0.080))
    run = fake_run(ring, window)
    assert waits.slow_step_starved_ms(run, "train/step", ["train/block"]) \
        is None
    (row,) = waits.stalls(run, "train/step", ["train/block"])
    assert not set(row) & {"starved_us", "runq_us", "pressure_us"}
    assert waits.slow_step_starved_ms(
        fake_run(None, window), "train/step", ["train/block"]) is None
    # the profiler's own step is left out here too
    ring, window = steady_run(
        block=(7, 0.150), host=lambda i: dict(QUIET, runq_us=150000 * (i == 7)))
    ends = [r["end"] + 0.001 - window[0]
            for r, _ in program.steps(fake_run(ring, window), "train/step")]
    traced = fake_run(ring, window, trace=True,
                      seconds=ends[7] + 4.0 - 0.0005,
                      samples={"step_end_s": ends})
    plain = fake_run(ring, window, samples={"step_end_s": ends})
    args = ("train/step", ["train/block"])
    assert waits.slow_step_starved_ms(traced, *args) == 0.0
    assert waits.slow_step_starved_ms(plain, *args) == pytest.approx(150.0)


OP = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f"


def synthetic_trace(busy, modules=(), window=(0.0, 10.0)):
    """A reduced trace of len(busy) chips: busy[chip] = [(start, end)] of its
    ops, modules = [(name, start, end)] of the first chip's programs."""
    planes = {"/host:CPU": {"python3": [(xplane.WINDOW_SPAN, *window)]}}
    for chip, ivs in enumerate(busy):
        planes[f"/device:TPU:{chip}"] = {
            "XLA Ops": [(OP, a, b) for a, b in ivs],
            "XLA Modules": list(modules) if chip == 0 else []}
    return xplane.Trace(planes)


def serial_driver(gaps, window):
    """The driving thread of a serial engine over a chip idle in `gaps`: in
    each gap of length L the wait for the last program closes a quarter in,
    the host's own code takes the next quarter, then the enqueue opens and
    the wait for its program follows it, up to a quarter into the next gap.
    One serve/step spans it all."""
    evs = [(P + "serve/step", window[0], window[1], 1)]
    t_wait = window[0]
    for a, b in gaps:
        span = b - a
        evs += [(P + "serve/decode/wait", t_wait, a + 0.25 * span, None),
                (P + "serve/decode/enqueue", a + 0.5 * span,
                 a + 0.6 * span, None)]
        t_wait = a + 0.6 * span
    evs.append((P + "serve/decode/wait", t_wait, window[1], None))
    return sorted(evs, key=lambda e: e[1])


def test_round_trip_and_the_rest_sum_to_the_idle_inside_the_program(trace):
    gaps = [g for g in xplane.subtract([trace.window], trace.busy(0))]
    idle = sum(b - a for a, b in gaps)
    assert len(gaps) >= 3 and idle > 0
    driver = serial_driver(gaps, trace.window)
    scale = 100.0 / trace.window_seconds()
    # of each gap: a quarter the wake, a quarter the host's own code, half
    # the launch
    round_trip, inside = waits.roundtrip_idle(
        trace, driver, "serve/decode/enqueue", "serve/decode/wait")
    assert round_trip == pytest.approx(0.75 * idle * scale)
    assert inside == pytest.approx(
        program.split_idle(trace, [trace.window])[0])
    assert inside - round_trip == pytest.approx(0.25 * idle * scale)
    # a host that is outside the program's spans for the first half of every
    # gap: what is left is inside the program, and all of it round trip
    late = [e for e in driver if e[0] == P + "serve/decode/enqueue"] + [
        (P + "serve/decode/wait", a + 0.6 * (b - a), b, None)
        for a, b in gaps]
    round_trip, inside = waits.roundtrip_idle(
        trace, sorted(late, key=lambda e: e[1]), "serve/decode/enqueue",
        "serve/decode/wait")
    assert round_trip == pytest.approx(0.5 * idle * scale)
    assert inside == pytest.approx(round_trip) == pytest.approx(
        program.split_idle(
            trace, xplane.union((a, b) for _, a, b, _ in late))[0])


def test_the_round_trip_is_a_mean_over_four_chips():
    tr = synthetic_trace(([(0.0, 4.0), (6.0, 10.0)], [(0.0, 4.0), (5.0, 10.0)],
                          [(0.0, 3.0), (6.0, 10.0)], [(0.0, 10.0)]))
    # the wait closes at 4.5, the enqueue opens at 5.5: chip 0 woke for 0.5,
    # its own code took 1.0, it launched for 0.5; chip 1 was busy again
    # before the enqueue; chip 2 went idle a second early
    driver = [(P + "train/step", 0.0, 10.0, 3),
              (P + "train/block", 0.0, 4.5, None),
              (P + "train/enqueue", 5.5, 5.6, None),
              (P + "train/block", 5.6, 10.0, None)]
    round_trip, inside = waits.roundtrip_idle(
        tr, driver, "train/enqueue", "train/block")
    assert round_trip == pytest.approx(
        100.0 * ((0.5 + 0.5) + (0.5 + 0.0) + (1.5 + 0.5) + 0.0) / 40)
    assert inside == pytest.approx(100.0 * (2 + 1 + 3 + 0) / 40)
    assert (inside, 0.0) == pytest.approx(
        program.split_idle(tr, [(0.0, 10.0)]))


def serial_steps(n, shown_early, launch, wake, program_s=0.010,
                 host_s=0.001, long=None):
    """n serial steps from t=1: enqueue opens, its program starts `launch(i)`
    later and runs `program_s` (`long`: {i: seconds more}), the wait closes
    `wake(i)` after it ended, the host's own code takes `host_s`. The
    device's times are shown `shown_early` seconds early. Returns (driver
    events, module events)."""
    driver, modules, t = [], [], 1.0
    for i in range(n):
        t0 = t
        start = t + launch(i)
        end = start + program_s + (long or {}).get(i, 0.0)
        close = end + wake(i)
        modules.append(("jit__unknown(7)", start - shown_early,
                        end - shown_early))
        driver += [(P + "serve/decode/enqueue", t, t + 0.0002, None),
                   (P + "serve/decode/put", t, t + 0.0001, None),
                   (P + "serve/decode/wait", t + 0.0003, close, None)]
        t = close + host_s
        driver.append((P + "serve/step", t0 - 0.0001, t - 0.0001, i))
    return sorted(driver, key=lambda e: e[1]), modules


@pytest.mark.parametrize("shown_early", [0.0004, -0.0003, 0.0])
def test_the_skew_interval_contains_a_known_offset(shown_early):
    driver, modules = serial_steps(
        40, shown_early, launch=lambda i: 0.0003 + 0.0002 * (i % 5) / 4,
        wake=lambda i: 0.0002 + 0.0002 * (i % 7) / 6)
    tr = synthetic_trace(([(a, b) for _, a, b in modules],), modules,
                         window=(0.5, 2.0))
    skew = waits.clock_skew(tr, driver, "serve/decode/enqueue",
                            "serve/decode/wait", "jit__unknown")
    assert skew["lo_ms"] <= 1e3 * shown_early <= skew["hi_ms"]
    # as wide as the two smallest latencies together: 0.3 + 0.2 ms
    assert skew["width_ms"] == pytest.approx(0.5)
    assert skew["lo_ms"] == pytest.approx(1e3 * shown_early - 0.3)
    assert skew["enqueues"] == skew["waits"] == 40
    assert skew["launch_ms_p50"] == pytest.approx(0.4 - 1e3 * shown_early)
    # the inputs' transfer closes 0.1 ms into the enqueue, before the call
    tight = waits.clock_skew(tr, driver, "serve/decode/enqueue",
                             "serve/decode/wait", "jit__unknown",
                             after="serve/decode/put")
    assert tight["width_ms"] == pytest.approx(0.4)
    assert tight["lo_ms"] <= 1e3 * shown_early <= tight["hi_ms"] \
        == skew["hi_ms"]
    assert tight["launch_ms_p50"] == skew["launch_ms_p50"]
    assert waits.clock_skew(tr, driver, "serve/decode/enqueue",
                            "serve/decode/wait", "jit_other") is None


@pytest.mark.parametrize("shown_early", [0.0009, -0.0006, 0.0])
def test_the_round_trip_is_the_same_anywhere_in_the_clocks_interval(
        shown_early, capsys):
    """A device clock that runs 0.9 ms early shows every program starting
    before its enqueue opened (my chip run, PR 36: GLM's launch gap p50
    -0.544 ms as the trace showed it): read as they are, the times show the
    chip busy again while the host's own code still runs, and count that
    idle time inside the wait instead."""
    driver, modules = serial_steps(
        40, shown_early, launch=lambda i: 0.0003 + 0.0002 * (i % 5) / 4,
        wake=lambda i: 0.0002 + 0.0002 * (i % 7) / 6)
    window = (driver[0][1], driver[-1][2])
    tr = synthetic_trace(([(a, b) for _, a, b in modules],), modules,
                         window=window)
    names = ("serve/decode/enqueue", "serve/decode/wait")
    skew = waits.clock_skew(tr, driver, *names, "jit__unknown")
    lo, hi = skew["lo_ms"] / 1e3, skew["hi_ms"] / 1e3
    got = [waits.roundtrip_idle(tr, driver, *names, shift=x)
           for x in (lo, (lo + hi) / 2, hi)]
    assert got[0] == pytest.approx(got[1]) and got[2] == pytest.approx(got[1])
    # all the idle time but the host's 1 ms a step between wait and enqueue
    round_trip, inside = got[1]
    scale = 100.0 / (window[1] - window[0])
    assert inside - round_trip == pytest.approx(39 * 0.001 * scale, rel=0.03)
    # outside the interval the split is another
    if shown_early > 0.0005:
        assert waits.roundtrip_idle(tr, driver, *names)[0] \
            > 1.01 * round_trip
    run = types.SimpleNamespace(on_tpu=True, reduced_trace=lambda: tr,
                                _waits_host=(driver, []))
    assert waits.idle_roundtrip_share(run, *names, "jit__unknown") \
        == pytest.approx(round_trip)
    assert "width 0.500" in capsys.readouterr().out


def test_an_empty_interval_reads_no_round_trip(capsys):
    """Spans and programs that do not belong together (a wait that closes
    before its program ended): None and a line in the log, not a share."""
    driver, modules = serial_steps(
        20, 0.0, launch=lambda i: 0.0004, wake=lambda i: 0.0003)
    early = [(n, a, b - 0.001 * (n == P + "serve/decode/wait"), s)
             for n, a, b, s in driver]
    tr = synthetic_trace(([(a, b) for _, a, b in modules],), modules,
                         window=(0.5, 2.0))
    run = types.SimpleNamespace(on_tpu=True, reduced_trace=lambda: tr,
                                _waits_host=(early, []))
    assert waits.idle_roundtrip_share(
        run, "serve/decode/enqueue", "serve/decode/wait",
        "jit__unknown") is None
    assert "an empty interval" in capsys.readouterr().out


def traced_run(ring, window, tr, driver, runtime=()):
    run = fake_run(ring, window)
    run.on_tpu, run.reduced_trace = True, lambda: tr
    run._waits_host = (driver, list(runtime))
    return run


def test_a_planted_long_program_lands_in_device_ms():
    # the ring is on perf_counter from 100, the trace on its own clock from 1
    driver, modules = serial_steps(
        30, 0.0, launch=lambda i: 0.0004, wake=lambda i: 0.0003,
        long={12: 0.090, 20: 0.0001})
    ring = Ring()
    for name, a, b, step in driver:
        if name == P + "serve/step":
            kids = [e for e in driver if a <= e[1] and e[2] <= b
                    and e[0] != name]
            sid = ring._id + len(kids) + 1
            for kn, ka, kb, _ in kids:
                ring.add(kn[len(P):], ka + 99.0, kb + 99.0, parent=sid,
                         **(QUIET if kn.endswith("wait") else {}))
            ring.add("serve/step", a + 99.0, b + 99.0, step=step)
    tr = synthetic_trace(([(a, b) for _, a, b in modules],), modules,
                         window=(1.05, 1.5))
    # the runtime's threads: something long before the stalled program's
    # end, then its completion 0.2 ms after that end, then the copy
    end = [b for _, _, b in modules][12]
    runtime = [("pjrt-tpu-tasks/314", "D2H Dispatch", 1.06, 1.0601),
               ("futex-default/415", "ReadSyncFlag", end + 0.0002,
                end + 0.00025),
               ("pjrt-tpu-tasks/314", "D2H Dispatch", end + 0.00026,
                end + 0.00029),
               ("main/279", "Execute", end + 0.002, end + 0.0021)]
    run = traced_run(ring, (100.0, 101.0), tr, driver, runtime)
    args = ("serve/step", ["serve/decode/wait"], "serve/decode/enqueue",
            "jit__unknown")
    assert waits.slow_step_device_ms(run, *args) == pytest.approx(90.0)
    (row,) = waits.stalls(run, *args)
    assert row["step"] == 12
    assert row["device_ms"] == pytest.approx(100.0)
    assert row["launch_gap_ms"] == pytest.approx(0.4)
    assert row["wake_gap_ms"] == pytest.approx(0.3)
    assert row["runtime_first"] == {
        "line": "futex-default/415", "name": "ReadSyncFlag",
        "after_ms": pytest.approx(0.2), "ms": pytest.approx(0.05),
        "of": 2}
    assert row["excess_by_span_ms"] == {
        "serve/decode/wait": pytest.approx(90.0)}
    # a stall of the host inside the wait, the program as long as ever: 0
    driver, modules = serial_steps(
        30, 0.0, launch=lambda i: 0.0004,
        wake=lambda i: 0.0903 if i == 12 else 0.0003)
    tr = synthetic_trace(([(a, b) for _, a, b in modules],), modules,
                         window=(1.05, 1.5))
    run = traced_run(ring, (100.0, 101.0), tr, driver)
    assert waits.slow_step_device_ms(run, *args) == pytest.approx(0.0)
    (row,) = waits.stalls(run, *args)
    assert row["wake_gap_ms"] == pytest.approx(90.3)
    # outside the traced window, or without a trace: nothing of the trace's
    run = traced_run(ring, (100.0, 101.0), synthetic_trace(
        ([(a, b) for _, a, b in modules],), modules, window=(1.2, 1.5)),
        driver)
    assert waits.slow_step_device_ms(run, *args) == 0.0
    assert "device_ms" not in waits.stalls(run, *args)[0]
    assert waits.slow_step_device_ms(
        fake_run(ring, (100.0, 101.0)), *args) is None


def test_the_trace_readers_read_nothing_at_a_program_without_wait_spans(
        trace, monkeypatch):
    from paddle_tpu.monitor import flight

    # a serial host around the recorded programs: each enqueue opens 0.1 ms
    # before its program starts, each wait closes 0.1 ms after it ended
    progs, _ = waits._programs(trace, "jit_")
    assert len(progs) >= 2
    driver = [(P + "serve/step", *trace.window, 1)]
    for a, b in progs:
        driver += [(P + "serve/decode/enqueue", a - 1e-4, a - 5e-5, None),
                   (P + "serve/decode/wait", a - 5e-5, b + 1e-4, None)]
    driver.sort(key=lambda e: e[1])
    run = types.SimpleNamespace(on_tpu=True, trace_file=PROBE,
                                reduced_trace=lambda: trace,
                                _waits_host=(driver, []))
    args = ("serve/decode/enqueue", "serve/decode/wait", "jit_")
    assert 0 < waits.idle_roundtrip_share(run, *args) \
        <= program.split_idle(trace, [trace.window])[0]
    # the recorded trace dates from before the program had spans
    run = types.SimpleNamespace(on_tpu=True, trace_file=PROBE,
                                reduced_trace=lambda: trace)
    assert waits.host_plane(PROBE)[0] == []
    # the runtime's threads are there (no line of it holds a window span)
    assert "main/279" in {line for line, *_ in waits.host_plane(PROBE)[1]}
    assert waits.idle_roundtrip_share(run, *args) is None
    # the parent's program: spans in the trace, no wait span in the program
    run = types.SimpleNamespace(on_tpu=True, trace_file=PROBE,
                                reduced_trace=lambda: trace,
                                _waits_host=(driver, []))
    monkeypatch.delattr(flight, "wait_span")
    assert waits.idle_roundtrip_share(run, *args) is None
    assert waits.slow_step_device_ms(
        run, "serve/step", ["serve/decode/wait"], *args[::2]) is None
    # and on the CPU
    cpu = fake_run(None, (0.0, 1.0))
    assert waits.idle_roundtrip_share(cpu, *args) is None


# -- a toy cell, by the one command -----------------------------------------

def test_a_toy_cell_reports_the_hidden_prepare(tree):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": toy_tree.REPO}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "tpubench/run.py", "--workload", "toy-offline",
         "--seed", str(2 ** 31 + 7), "--seconds", "1.5", "--trace", "1"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.splitlines()[-1])
    assert line["correct"] is True
    metrics = line["metrics"]
    assert metrics["prepare_ahead_ms_p50.offline"]["value"] > 0
    # what reads the device trace reads nothing on the CPU
    assert not [m for m in metrics
                if m.startswith(("idle_", "slow_step_device_ms"))]


def test_the_steps_program_is_the_one_with_the_most_device_seconds():
    """On four chips a small program shards the batch twice a train step
    (my chip run, PR 36: the most frequent `jit_` module there): the
    step's program is still the one the gaps are taken to, and a device
    clock 2 ms off the host's still finds each step its own."""
    driver, modules = serial_steps(
        20, 0.002, launch=lambda i: 0.0004, wake=lambda i: 0.0003)
    small = [("jit__unknown_slice(3)", a - 0.0009 + k * 0.0001,
              a - 0.00085 + k * 0.0001)
             for _, a, _ in modules for k in (0, 1)]
    tr = synthetic_trace(([(a, b) for _, a, b in modules],),
                         sorted(modules + small, key=lambda m: m[1]),
                         window=(0.5, 2.0))
    progs, usual = waits._programs(tr, "jit__unknown")
    assert len(progs) == 20 and usual == pytest.approx(0.010)
    skew = waits.clock_skew(tr, driver, "serve/decode/enqueue",
                            "serve/decode/wait", "jit__unknown")
    assert skew["enqueues"] == skew["waits"] == 20
    assert skew["lo_ms"] <= 2.0 <= skew["hi_ms"]
    assert skew["width_ms"] == pytest.approx(0.7)
    assert waits._programs(tr, "jit_nothing") == ([], None)
