"""Performance attribution plane (paddle_tpu.monitor.perf + the jit
capture sites + benchmarks/regress.py) — the compute axis of the
telemetry stack: per-program cost ledger parity between
jit.cache_report() and the perf/program/* gauges, the
PADDLE_PERF_PROGRAM=0 zero-counter contract, roofline verdict
boundaries, StepTimer's step/attrib/* decomposition, the CLI `perf`
text/--json round-trip (live + dump bundle), fleet slowest-program
attribution, and the bench-trail regression gate's noise bands +
exit-2 contract."""
import json
import os
import sys
import time

import numpy as np
import pytest

import jax
import paddle_tpu as paddle
from paddle_tpu.core import monitor as core_monitor
from paddle_tpu.monitor import fleet, flight, perf
from paddle_tpu.monitor.cli import main as cli_main

BENCH_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)
import regress  # noqa: E402


@pytest.fixture(autouse=True)
def _clean(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_FLIGHT_DIR", str(tmp_path))
    flight.recorder.clear()
    yield
    flight.uninstall_excepthook()


# ---------------------------------------------------------------------------
# cost_analysis extraction + ledger parity
# ---------------------------------------------------------------------------

class _FakeCompiled:
    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca


def test_extract_cost_analysis_normalizes_shapes():
    want = {"flops": 10, "bytes_accessed": 20, "transcendentals": 0}
    d = {"flops": 10.0, "bytes accessed": 20.0}
    assert perf.extract_cost_analysis(_FakeCompiled(d)) == want
    # older jax wraps the per-computation dict in a list
    assert perf.extract_cost_analysis(_FakeCompiled([d])) == want
    assert perf.extract_cost_analysis(_FakeCompiled([])) is None
    assert perf.extract_cost_analysis(
        _FakeCompiled(RuntimeError("no analysis"))) is None


def test_extract_cost_analysis_clamps_unknown_negative():
    """XLA reports -1 for "unknown" on some backends — a negative
    FLOP count would poison every downstream ratio."""
    out = perf.extract_cost_analysis(_FakeCompiled(
        {"flops": -1.0, "bytes accessed": 64.0,
         "transcendentals": "bogus"}))
    assert out == {"flops": 0, "bytes_accessed": 64,
                   "transcendentals": 0}


def test_cache_report_train_step_cost_matches_gauges():
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStepCompiler, cache_report

    # unique class name: gauge + cache_report fn are keyed by
    # type(model).__name__, and other suites also compile Linear steps
    class PerfLedgerLinear(nn.Linear):
        pass

    paddle.seed(0)
    net = PerfLedgerLinear(16, 8)
    ce = nn.CrossEntropyLoss()
    opt = optim.Adam(learning_rate=1e-3, parameters=net.parameters())
    step = TrainStepCompiler(net, opt, lambda o, y: ce(o, y))
    x = paddle.to_tensor(np.random.randn(8, 16).astype(np.float32))
    y = paddle.to_tensor(np.random.randint(0, 8, (8,)).astype(np.int64))
    step(x, y)
    ent = next(e for e in cache_report()
               if e["kind"] == "train_step"
               and e["fn"] == "PerfLedgerLinear" and e.get("cost"))
    cost = ent["cost"]
    assert cost["flops"] > 0  # a matmul fwd+bwd is real FLOPs
    assert cost["bytes_accessed"] > 0
    for key in ("flops", "bytes_accessed", "transcendentals"):
        assert core_monitor.stat_get(
            f"perf/program/train_step:PerfLedgerLinear/{key}") \
            == cost[key], key
    # the ledger walk surfaces the same numbers under the same name
    assert perf.program_costs()[
        "train_step:PerfLedgerLinear"]["flops"] == cost["flops"]


def test_to_static_cost_per_entry_and_dispatch_hist():
    from paddle_tpu.jit import cache_report, to_static

    @to_static
    def perf_poly(v):
        return v @ v + v

    a = paddle.to_tensor(np.ones((32, 32), np.float32))
    perf_poly(a)  # fresh compile — excluded from the dispatch hist
    perf_poly(a)
    ent = next(e for e in cache_report()
               if e["kind"] == "to_static"
               and e["fn"].split(".")[-1] == "perf_poly")
    assert len(ent["cost"]) == len(ent["keys"])
    assert ent["cost"][0]["flops"] >= 2 * 32 * 32 * 32  # the matmul
    fname = perf_poly._telemetry_key
    snap = core_monitor.registry.snapshot_histograms().get(
        f"jit/hist/{fname}/dispatch_us")
    assert snap and snap["count"] == 1  # compile call excluded


def test_first_dispatch_excluded_from_hist():
    """The first call of a fresh program runs the lazy XLA compile
    inline — timing it would poison the p99 with compile time."""
    from paddle_tpu.jit import to_static

    @to_static
    def perf_first(v):
        return v + 1

    a = paddle.to_tensor(np.ones((4, 4), np.float32))
    perf_first(a)
    fname = perf_first._telemetry_key
    key = f"jit/hist/{fname}/dispatch_us"
    snap = core_monitor.registry.snapshot_histograms().get(key)
    assert snap is None or snap["count"] == 0
    perf_first(a)
    snap = core_monitor.registry.snapshot_histograms()[key]
    assert snap["count"] == 1


def test_program_capture_env_off_zero_gauges(monkeypatch):
    from paddle_tpu.jit import cache_report, to_static

    monkeypatch.setenv("PADDLE_PERF_PROGRAM", "0")

    @to_static
    def perf_poly_off(v):
        return v * v

    perf_poly_off(paddle.to_tensor(np.ones((8, 8), np.float32)))
    ent = next(e for e in cache_report()
               if e["kind"] == "to_static"
               and e["fn"].split(".")[-1] == "perf_poly_off")
    assert ent["cost"] == [None]
    # zero-counter contract: the disarmed plane leaves NO gauges
    fname = perf_poly_off._telemetry_key
    assert not [k for k in core_monitor.registry.snapshot()
                if k.startswith(f"perf/program/{fname}")]
    # the memory ledger (its own knob) still captured off the shared
    # compile — the two opt-outs are independent
    assert ent["memory"][0] and ent["memory"][0]["argument_bytes"] > 0


def test_dispatch_timing_env_off(monkeypatch):
    from paddle_tpu.jit import to_static

    monkeypatch.setenv("PADDLE_PERF_DISPATCH", "0")

    @to_static
    def perf_poly_async(v):
        return v - 1

    a = paddle.to_tensor(np.ones((4, 4), np.float32))
    perf_poly_async(a)
    perf_poly_async(a)
    fname = perf_poly_async._telemetry_key
    snap = core_monitor.registry.snapshot_histograms().get(
        f"jit/hist/{fname}/dispatch_us")
    assert snap is None or snap["count"] == 0


# ---------------------------------------------------------------------------
# peak table + roofline math
# ---------------------------------------------------------------------------

def test_device_peaks_cpu_fallback_and_env_overrides(monkeypatch):
    pk = perf.device_peaks()
    assert pk["matched"] in perf.PEAK_TABLE
    assert pk["peak_tflops"] > 0 and pk["hbm_gbps"] > 0
    monkeypatch.setenv("PADDLE_PEAK_TFLOPS", "123.5")
    monkeypatch.setenv("PADDLE_HBM_GBPS", "456")
    monkeypatch.setenv("PADDLE_ICI_GBPS", "7.5")
    pk = perf.device_peaks()
    assert pk["peak_tflops"] == 123.5
    assert pk["hbm_gbps"] == 456.0
    assert pk["ici_gbps"] == 7.5


def test_bench_peak_source_agrees_with_perf_table(monkeypatch):
    """Satellite 1: bench.py's MFU column reads the SAME peak the
    per-program MFU uses (BENCH_PEAK_TFLOPS still wins for old
    trails)."""
    repo = os.path.dirname(BENCH_DIR)
    if repo not in sys.path:
        sys.path.insert(0, repo)
    import bench

    monkeypatch.delenv("BENCH_PEAK_TFLOPS", raising=False)
    assert bench.peak_tflops() == perf.device_peaks()["peak_tflops"]
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "321")
    assert bench.peak_tflops() == 321.0


def test_roofline_verdict_boundaries():
    # peak 100 TF/s over 1000 GB/s -> machine balance 100 flops/byte
    v = perf.roofline_verdict
    assert v(1000, 1, 100.0, 1000.0) == "compute-bound"
    assert v(100, 1, 100.0, 1000.0) == "compute-bound"  # at balance
    assert v(99, 1, 100.0, 1000.0) == "HBM-bound"
    assert v(0, 64, 100.0, 1000.0) == "unknown"
    assert v(64, 0, 100.0, 1000.0) == "unknown"
    # the comm leg trumps the intensity comparison entirely
    assert v(1000, 1, 100.0, 1000.0, comm_frac=0.51) == "comm-bound"


def test_perf_report_offline_mfu_and_comm_math():
    """perf_report over synthetic registries: achieved FLOP/s from
    the p50 dispatch, MFU against the supplied peaks, comm fraction
    from wire bytes vs the interconnect."""
    core_monitor.hist_observe("jit/hist/offline_prog/dispatch_us",
                              1000.0)
    hists = core_monitor.registry.snapshot_histograms()
    stats = {
        "perf/program/offline_prog/flops": 2_000_000_000,
        "perf/program/offline_prog/bytes_accessed": 1_000_000,
        "perf/program/offline_prog/transcendentals": 0,
    }
    peaks = {"device_kind": "test", "matched": "v5e",
             "peak_tflops": 100.0, "hbm_gbps": 1000.0,
             "ici_gbps": 100.0}
    rep = perf.perf_report(stats=stats, hists=hists, peaks=peaks)
    ent = rep["programs"]["offline_prog"]
    assert ent["intensity"] == 2000.0  # 2 GF / 1 MB
    assert ent["verdict"] == "compute-bound"
    assert ent["dispatch"]["count"] == 1
    # 2 GF in ~1 ms ~= 2 TFLOP/s achieved -> MFU ~2% of the 100 TF
    # peak (p50 lands inside the observation's log bucket, not
    # exactly on it)
    assert 500.0 < ent["achieved_gflops"] < 8000.0
    assert ent["mfu"] == pytest.approx(
        ent["achieved_gflops"] / 1e3 / 100.0, rel=1e-3)
    # now drown the run in wire bytes: comm-bound everywhere
    stats["comm/allreduce/wire_bytes"] = 10**12
    rep = perf.perf_report(stats=stats, hists=hists, peaks=peaks)
    assert rep["comm"]["frac"] > 0.5
    assert rep["programs"]["offline_prog"]["verdict"] == "comm-bound"


# ---------------------------------------------------------------------------
# step-time decomposition
# ---------------------------------------------------------------------------

def test_step_attrib_decomposition_bounded_by_step():
    from paddle_tpu import monitor

    st = monitor.StepTimer()
    st.begin_step()
    flight.record("dispatch_end", name="p", dur_us=200)
    flight.record("io_fetch", us=100)
    flight.record("collective_end", op="allreduce", dur_us=50)
    time.sleep(0.005)
    dt = st.end_step(batch_size=1)
    dt_us = int(dt * 1e6)
    got = {w: core_monitor.stat_get(f"step/attrib/{w}_us")
           for w in ("device", "host", "io", "comm")}
    assert got["device"] == 200
    assert got["io"] == 100
    assert got["comm"] == 50
    assert sum(got.values()) <= dt_us  # never exceeds the step
    assert got["host"] == dt_us - 350


def test_step_attrib_scale_clamps_overreported_spans():
    """Span durations can exceed the step wall (overlapping async
    work) — the decomposition scales down instead of reporting a
    >100% step."""
    from paddle_tpu import monitor

    st = monitor.StepTimer()
    st.begin_step()
    flight.record("dispatch_end", name="p", dur_us=10**9)
    dt = st.end_step(batch_size=1)
    dt_us = int(dt * 1e6)
    assert core_monitor.stat_get("step/attrib/host_us") == 0
    assert core_monitor.stat_get("step/attrib/device_us") <= dt_us


def test_step_attrib_env_off(monkeypatch):
    from paddle_tpu import monitor

    monkeypatch.setenv("PADDLE_PERF_STEP", "0")
    for w in ("device", "host", "io", "comm"):
        core_monitor.stat_reset(f"step/attrib/{w}_us")
    st = monitor.StepTimer()
    st.begin_step()
    flight.record("dispatch_end", name="p", dur_us=200)
    st.end_step(batch_size=1)
    assert core_monitor.stat_get("step/attrib/device_us") == 0


# ---------------------------------------------------------------------------
# CLI round-trips + profiler counters
# ---------------------------------------------------------------------------

def _run_program():
    from paddle_tpu.jit import to_static

    @to_static
    def perf_cli_prog(v):
        return v @ v

    a = paddle.to_tensor(np.ones((16, 16), np.float32))
    perf_cli_prog(a)
    perf_cli_prog(a)
    return perf_cli_prog._telemetry_key


def test_cli_perf_live_text_and_json(capsys):
    fname = _run_program()
    assert cli_main(["perf"]) == 0
    out = capsys.readouterr().out
    assert "roofline ledger" in out
    assert fname.split(".")[-1] in out
    assert cli_main(["perf", "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    ent = rep["programs"][fname]
    assert ent["flops"] >= 2 * 16 * 16 * 16
    assert ent["dispatch"]["count"] >= 1
    assert ent["verdict"] in ("compute-bound", "HBM-bound",
                              "comm-bound", "unknown")
    assert rep["peaks"]["matched"] in perf.PEAK_TABLE


def test_cli_perf_dump_bundle_roundtrip(tmp_path, capsys):
    fname = _run_program()
    path = flight.write_dump("sigusr1")
    assert cli_main(["perf", path]) == 0
    out = capsys.readouterr().out
    assert fname.split(".")[-1] in out
    # non-telemetry JSON is the exit-2 contract, not a traceback
    bad = tmp_path / "not_a_bundle.json"
    bad.write_text(json.dumps({"foo": 1}))
    assert cli_main(["perf", str(bad)]) == 2


def test_profiler_trace_carries_perf_counters(tmp_path):
    from paddle_tpu import profiler

    _run_program()
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    with prof:
        prof.step(num_samples=1)
    trace = tmp_path / "trace.json"
    prof.export(str(trace))
    evs = json.load(open(trace))["traceEvents"]
    names = {e.get("name") for e in evs if e.get("ph") == "C"}
    assert any(n and n.startswith("perf/program/") for n in names)


def test_fleet_slowest_program_names_the_program():
    core_monitor.hist_observe("jit/hist/fleet_a/dispatch_us", 100.0)
    core_monitor.hist_observe("jit/hist/fleet_b/dispatch_us", 900.0)
    core_monitor.hist_observe("jit/hist/fleet_b/dispatch_us", 900.0)
    # only the two histograms made here: the registry is the process's,
    # and another test's jit/hist/*/dispatch_us in this worker may sum
    # to more than fleet_b's
    hists = {k: v for k, v
             in core_monitor.registry.snapshot_histograms().items()
             if "/fleet_" in k}
    prog = fleet.slowest_program(hists)
    assert prog["program"] == "fleet_b"  # max by SUM, not one sample
    assert prog["count"] == 2 and prog["total_us"] >= 1800
    assert fleet.slowest_program({}) is None
    # a straggling rank's report entry names its slowest program
    mk = {"step/count": 10}
    recs = [
        {"rank": 0, "stats": dict(mk, **{"step/total_time_us": 1e6})},
        {"rank": 1, "stats": dict(mk, **{"step/total_time_us": 1e6})},
        {"rank": 2, "stats": dict(mk, **{"step/total_time_us": 5e6}),
         "hists": hists},
    ]
    rep = fleet.straggler_report(recs, threshold=1.25)
    entry = next(s for s in rep["stragglers"] if s["rank"] == 2)
    assert entry["slowest_program"]["program"] == "fleet_b"


# ---------------------------------------------------------------------------
# bench-trail regression gate
# ---------------------------------------------------------------------------

def _round(n, values, spread=(1.0, 1.01, 1.02), extra_sections=None):
    cfgs = {name: {"value": v, "unit": "imgs/s",
                   "window_spread": list(spread)}
            for name, v in values.items()}
    cfgs.update(extra_sections or {})
    return {"n": n, "parsed": {"extra": cfgs}}


def _write_trail(root, *rounds):
    for rec in rounds:
        p = os.path.join(str(root), f"BENCH_r{rec['n']:02d}.json")
        with open(p, "w") as f:
            json.dump(rec, f)


def test_regress_clean_trail_passes(tmp_path, capsys):
    _write_trail(
        tmp_path,
        {"n": 1, "parsed": {}},  # pre-extra round: skipped, not fatal
        _round(2, {"a": 100.0, "b": 50.0}),
        _round(3, {"a": 98.0, "b": 51.0},
               extra_sections={"perf": {"enabled": True},
                               "telemetry": {"stats": {}}}))
    assert regress.main(["--root", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "r03 vs r02" in out
    assert "REGRESSION" not in out


def test_regress_regression_exits_2(tmp_path, capsys):
    _write_trail(tmp_path,
                 _round(2, {"a": 100.0, "b": 50.0}),
                 _round(3, {"a": 40.0, "b": 50.0}))  # a fell 60%
    assert regress.main(["--root", str(tmp_path), "--json"]) == 2
    rows = json.loads(capsys.readouterr().out)["rows"]
    by = {r["config"]: r for r in rows}
    assert by["a"]["status"] == "regression"
    assert by["b"]["status"] == "ok"


def test_regress_missing_config_exits_2(tmp_path):
    _write_trail(tmp_path,
                 _round(2, {"a": 100.0, "b": 50.0}),
                 _round(3, {"a": 100.0}))  # b silently vanished
    assert regress.main(["--root", str(tmp_path)]) == 2


def test_regress_noise_band_from_window_spread(tmp_path, capsys):
    """A config whose own windows spread 50% gets a wide band — the
    same 40% drop that fails a quiet config passes a noisy one."""
    _write_trail(
        tmp_path,
        _round(2, {"noisy": 100.0, "quiet": 100.0}),
        {"n": 3, "parsed": {"extra": {
            "noisy": {"value": 61.0, "unit": "u",
                      "window_spread": [1.0, 1.2, 1.5]},
            "quiet": {"value": 61.0, "unit": "u",
                      "window_spread": [1.0, 1.01, 1.02]}}}})
    assert regress.main(["--root", str(tmp_path), "--json"]) == 2
    rows = json.loads(capsys.readouterr().out)["rows"]
    by = {r["config"]: r for r in rows}
    assert by["noisy"]["status"] == "ok"  # band ~0.417 from spread
    assert by["quiet"]["status"] == "regression"  # floor band 0.05
    assert by["noisy"]["band"] > by["quiet"]["band"]


def test_regress_current_file_mode(tmp_path):
    _write_trail(tmp_path, _round(2, {"a": 100.0}))
    cur = tmp_path / "out.json"
    cur.write_text(json.dumps(
        {"extra": {"a": {"value": 99.0, "unit": "u",
                         "window_spread": [1.0, 1.01]}}}))
    assert regress.main(["--root", str(tmp_path),
                         "--current", str(cur)]) == 0
    cur.write_text(json.dumps(
        {"extra": {"a": {"value": 9.0, "unit": "u",
                         "window_spread": [1.0, 1.01]}}}))
    assert regress.main(["--root", str(tmp_path),
                         "--current", str(cur)]) == 2


def test_regress_bad_input_exits_2(tmp_path, capsys):
    assert regress.main(["--root", str(tmp_path)]) == 2  # no rounds
    (tmp_path / "BENCH_r02.json").write_text("{not json")
    assert regress.main(["--root", str(tmp_path)]) == 2
    assert "error:" in capsys.readouterr().err


def test_regress_real_trail_is_clean():
    """The committed BENCH_r*.json trail must gate clean against
    itself — the gate ships armed."""
    trail = regress.load_trail()
    if len(trail) < 2:
        pytest.skip("repo trail has <2 rounds with extra")
    rows = regress.compare(trail[-2]["extra"], trail[-1]["extra"])
    assert regress.gate(rows) == 0, rows
