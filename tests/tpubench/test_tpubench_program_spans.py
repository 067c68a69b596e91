"""The readers over the program's own spans (tpubench/readers/program.py): on
synthetic spans, on the recorded v5e trace plus synthetic host events, and in
a toy cell run by the one command on the CPU."""
import json
import os
import subprocess
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy_tree  # noqa: E402

from tpubench import xplane  # noqa: E402
from tpubench.readers import program  # noqa: E402

PROBE = os.path.join(toy_tree.REPO, "tpubench", "testdata",
                     "probe.xplane.pb")
P = program.PREFIX


class Ring:
    """Builds the dicts flight.spans() gives, in closing order."""

    def __init__(self):
        self.spans, self._id = [], 0

    def add(self, name, start, end, parent=0, **ids):
        self._id += 1
        self.spans.append({"id": self._id, "parent": parent, "tid": 1,
                           "name": P + name, "start": start, "end": end,
                           "ids": ids})
        return self._id

    def train_step(self, t, block=0.290, prepare=0.002, finish=0.001,
                   n=0):
        """One steady train step beginning at t; returns its end."""
        end = t + 2 * prepare + 0.001 + block + finish
        sid = self._id + 6
        a = t
        for name, dur in (("train/prepare", prepare),
                          ("train/prepare", prepare),
                          ("train/enqueue", 0.001), ("train/block", block),
                          ("train/finish", finish)):
            self.add(name, a, a + dur, parent=sid)
            a += dur
        assert self.add("train/step", t, end, step=n) == sid
        return end


def fake_run(ring, window, trace=False, seconds=20.0, samples=None,
             counters=None):
    run = types.SimpleNamespace(
        window=window, trace=trace, seconds=seconds,
        traffic={"trace_seconds": 4}, samples=samples or {},
        counters=counters or {}, on_tpu=False)
    run._program_ring = ring.spans if ring is not None else None
    return run


def steady_run(n=20, **stalls):
    """n train steps from t=100, 4 ms of caller's code between them; stalls:
    {"block": (i, s)} lengthens step i's block by s seconds, "gap" the
    caller's code after it, "prepare" its two prepare spans together."""
    ring, t = Ring(), 100.0
    for i in range(n):
        extra = {k: v[1] for k, v in stalls.items() if v[0] == i}
        t = ring.train_step(t, block=0.290 + extra.get("block", 0.0),
                            prepare=0.002 + extra.get("prepare", 0.0) / 2,
                            n=i)
        t += 0.004 + extra.get("gap", 0.0)
    return ring, (100.0, t)


# -- the ring -------------------------------------------------------------

def test_step_medians_from_the_ring():
    ring, window = steady_run()
    run = fake_run(ring, window)
    assert len(program.steps(run, "train/step")) == 20
    assert program.step_ms_p50(
        run, "train/step", include=["train/prepare", "train/enqueue"]) \
        == pytest.approx(5.0)
    assert program.step_ms_p50(run, "train/step",
                               include=["train/finish"]) \
        == pytest.approx(1.0)
    # the root's own time less what is excluded: prepare x 2 + finish
    assert program.step_ms_p50(
        run, "train/step", exclude=["train/enqueue", "train/block"]) \
        == pytest.approx(5.0)
    # only the steps wholly inside the window count
    late = fake_run(ring, (window[0] + 0.01, window[1]))
    assert len(program.steps(late, "train/step")) == 19


def test_descendants_reach_their_step_through_other_spans():
    ring = Ring()
    ring.add("serve/decode/enqueue", 1.01, 1.02, parent=2)
    ring.add("serve/decode", 1.0, 1.2, parent=3)
    ring.add("serve/step", 0.9, 1.3, step=1)
    ring.add("serve/decode/enqueue", 5.0, 5.1)          # no step above it
    run = fake_run(ring, (0.0, 10.0))
    (root, kids), = program.steps(run, "serve/step")
    assert sorted(k["name"] for k in kids) == [
        P + "serve/decode", P + "serve/decode/enqueue"]
    assert program.step_ms_p50(
        run, "serve/step", exclude=["serve/decode/enqueue"]) \
        == pytest.approx(390.0)


def test_slow_step_excess_is_zero_without_a_slow_step():
    ring, window = steady_run()
    run = fake_run(ring, window)
    for part in ("host", "wait"):
        assert program.slow_step_excess_ms(
            run, "train/step", ["train/block"], part) == 0.0


@pytest.mark.parametrize("where,host,wait", [
    ("block", 0.0, 80.0),       # the device, or the wait for it
    ("prepare", 80.0, 0.0),     # inside the program, on the host
    ("gap", 80.0, 0.0),         # the caller's code between two steps
])
def test_slow_step_excess_splits_a_planted_stall(where, host, wait):
    ring, window = steady_run(**{where: (7, 0.080)})
    run = fake_run(ring, window)
    got = {part: program.slow_step_excess_ms(
        run, "train/step", ["train/block"], part)
        for part in ("host", "wait")}
    assert got["host"] == pytest.approx(host, abs=1e-6)
    assert got["wait"] == pytest.approx(wait, abs=1e-6)


def test_slow_step_excess_leaves_out_the_profilers_own_stall():
    # a traced run starts the profiler after the first step that ends in the
    # last trace_seconds: here step 7, whose gap holds 150 ms of it
    ring, window = steady_run(gap=(7, 0.150), block=(12, 0.080))
    ends = [r["end"] + 0.001 - window[0]
            for r, _ in program.steps(fake_run(ring, window), "train/step")]
    seconds = ends[7] + 4.0 - 0.0005
    traced = fake_run(ring, window, trace=True, seconds=seconds,
                      samples={"step_end_s": ends})
    plain = fake_run(ring, window, samples={"step_end_s": ends})
    args = ("train/step", ["train/block"])
    assert program.slow_step_excess_ms(traced, *args, "host") \
        == pytest.approx(0.0, abs=1e-6)
    assert program.slow_step_excess_ms(traced, *args, "wait") \
        == pytest.approx(80.0, abs=1e-6)
    assert program.slow_step_excess_ms(plain, *args, "host") \
        == pytest.approx(150.0, abs=1e-6)


def test_setup_seconds_count_nested_compiles_once():
    ring = Ring()
    ring.add("cache/load/train_step:M", 11.0, 14.0, parent=2)
    ring.add("compile/train_step", 10.0, 20.0)
    ring.add("compile/capture/train_step:M", 20.5, 26.5)
    ring.add("compile/serve_decode:M", 95.0, 105.0)     # runs into the window
    ring.add("train/step", 30.0, 31.0)
    run = fake_run(ring, (100.0, 120.0))
    assert program.setup_seconds(run, ["compile/*", "cache/load/*"]) \
        == pytest.approx(10.0 + 6.0 + 5.0)
    assert program.setup_seconds(run, ["compile/capture/*"]) \
        == pytest.approx(6.0)


def test_gauge_reads_the_close_of_the_window():
    run = fake_run(None, (0.0, 1.0), counters={"close": {
        "mem/program/serve_decode:GPT/temp_bytes": 8 * 2 ** 30,
        "mem/program/serve_prefill:GPT/temp_bytes": 6 * 2 ** 30}})
    assert program.gauge(run, ["mem/program/serve_decode:*/temp_bytes"],
                         scale=2 ** -30) == 8.0
    assert program.gauge(run, ["mem/program/train_step:*/temp_bytes"]) \
        is None


def test_a_program_without_spans_gives_nothing_to_read():
    """The parent of the PR that added the spans: no flight.spans, no
    paddle_tpu/ event in the trace. Every reader returns None."""
    run = fake_run(None, (100.0, 120.0), counters={"close": {}})
    assert program.step_ms_p50(run, "train/step",
                               include=["train/finish"]) is None
    assert program.slow_step_excess_ms(run, "train/step", ["train/block"],
                                       "host") is None
    assert program.setup_seconds(run, ["compile/*"]) is None
    assert program.gauge(run, ["mem/program/*"]) is None
    assert program.idle_in_program_share(run) is None      # no chip
    assert program.idle_outside_program_share(run) is None


# -- the trace ------------------------------------------------------------

@pytest.fixture(scope="module")
def trace():
    return xplane.Trace.from_file(PROBE)


def test_idle_inside_and_outside_sum_to_the_device_idle(trace):
    lo, hi = trace.window
    idle = 100.0 * (1.0 - trace.busy_seconds() / trace.window_seconds())
    third = (hi - lo) / 3
    for inside in ([(lo, hi)], [], [(lo + third, lo + 2 * third)],
                   [(lo - 1.0, lo + 0.2 * third), (hi - third, hi + 1.0)]):
        got_in, got_out = program.split_idle(trace, inside)
        assert got_in + got_out == pytest.approx(idle, abs=1e-9)
        assert got_in >= 0.0 and got_out >= 0.0
    assert program.split_idle(trace, [(lo, hi)])[1] == pytest.approx(0.0)
    assert program.split_idle(trace, [])[0] == 0.0
    # intervals are intersected: a host span that covers the first half of
    # one gap takes half of that gap, wherever the gap's middle lies
    gaps = xplane.subtract([trace.window], trace.busy(0))
    a, b = max(gaps, key=lambda g: g[1] - g[0])
    half, _ = program.split_idle(trace, [(a, (a + b) / 2)])
    assert half == pytest.approx(
        100.0 * (b - a) / 2 / trace.window_seconds())


def test_idle_split_is_the_mean_over_four_chips():
    op = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f"
    planes = {"/host:CPU": {"python3": [(xplane.WINDOW_SPAN, 0.0, 10.0)]}}
    for chip, busy in enumerate(([(0.0, 9.0)], [(1.0, 10.0)],
                                 [(0.0, 4.0), (6.0, 10.0)], [(0.0, 10.0)])):
        planes[f"/device:TPU:{chip}"] = {
            "XLA Ops": [(op, a, b) for a, b in busy], "XLA Modules": []}
    tr = xplane.Trace(planes)
    idle = 100.0 * (1.0 - tr.busy_seconds() / tr.window_seconds())
    assert idle == pytest.approx(100.0 * (1 + 1 + 2 + 0) / 40)
    # the host is inside the program from 0 to 5: chip 1's gap (0-1) and
    # half of chip 2's (4-5) are inside, chip 0's (9-10) is outside
    got_in, got_out = program.split_idle(tr, [(0.0, 5.0)])
    assert got_in == pytest.approx(100.0 * 2 / 40)
    assert got_out == pytest.approx(100.0 * 2 / 40)
    assert got_in + got_out == pytest.approx(idle)


def test_idle_split_reads_the_host_plane_of_the_runs_trace(trace,
                                                           monkeypatch):
    # the recorded trace dates from before the program had spans
    assert program.host_spans(PROBE) == []
    run = types.SimpleNamespace(on_tpu=True, trace_file=PROBE,
                                reduced_trace=lambda: trace)
    assert program.idle_in_program_share(run) is None
    assert program.idle_outside_program_share(run) is None
    # with the program's spans in the host plane: the two shares
    lo, hi = trace.window
    run = types.SimpleNamespace(on_tpu=True, trace_file=PROBE,
                                reduced_trace=lambda: trace)
    monkeypatch.setattr(program, "host_spans",
                        lambda path: [(lo, (lo + hi) / 2)])
    idle = 100.0 * (1.0 - trace.busy_seconds() / trace.window_seconds())
    assert program.idle_in_program_share(run) \
        + program.idle_outside_program_share(run) \
        == pytest.approx(idle, abs=1e-9)
    assert 0.0 < program.idle_in_program_share(run) < idle


# -- a toy cell, by the one command -----------------------------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The toy tree, with the toy cells appended to the new metrics'
    `workloads`, as a later PR's cell would be."""
    dst = str(tmp_path_factory.mktemp("bench"))
    toy_tree.build(dst)
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"].endswith(".train") and "train-345m-1chip" \
                in m.get("workloads", ()):
            m["workloads"].append("toy-train")
        elif m["name"].endswith(".offline"):
            m["workloads"].append("toy-offline")
    with open(path, "w") as f:
        json.dump(bench, f)
    return dst


@pytest.mark.parametrize("workload,seconds,expect,positive", [
    ("toy-train", 2, {
        "host_prepare_ms_p50.train", "host_finish_ms_p50.train",
        "slow_step_excess_host_ms.train", "slow_step_excess_wait_ms.train",
        "compile_s_in_setup.train", "capture_compile_s.train"},
     {"host_prepare_ms_p50.train", "host_finish_ms_p50.train",
      "compile_s_in_setup.train", "capture_compile_s.train"}),
    ("toy-offline", 1.5, {
        "engine_host_ms_p50.offline", "slow_step_excess_host_ms.offline",
        "slow_step_excess_wait_ms.offline", "compile_s_in_setup.offline",
        "capture_compile_s.offline", "decode_temp_gib.offline"},
     {"engine_host_ms_p50.offline", "compile_s_in_setup.offline",
      "decode_temp_gib.offline"}),
])
def test_a_toy_cell_reports_the_span_metrics(tree, workload, seconds,
                                             expect, positive):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": toy_tree.REPO}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "tpubench/run.py", "--workload", workload,
         "--seed", str(2 ** 31 + 5), "--seconds", str(seconds), "--trace",
         "1"], cwd=tree, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.splitlines()[-1])
    assert line["correct"] is True
    metrics = line["metrics"]
    assert expect <= set(metrics)
    assert all(metrics[m]["value"] > 0 for m in positive)
    assert all(metrics[m]["value"] >= 0 for m in expect)
    # what reads the device trace reads nothing on the CPU
    assert not [m for m in metrics if m.startswith("idle_")]
