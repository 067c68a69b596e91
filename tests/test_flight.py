"""Flight recorder + hang/crash forensics (paddle_tpu.monitor.flight
+ the `python -m paddle_tpu.monitor` CLI) — the failure-time black box
the reference stack provides via VLOG trails and distributed hang
dumps: a stalled collective must produce a per-rank watchdog dump
(stacks + flight-ring tail + telemetry snapshot) without hanging the
suite, an unhandled exception must leave an inspectable crash bundle,
and per-rank chrome traces must merge into one Perfetto file."""
import glob
import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor
from paddle_tpu.monitor import flight
from paddle_tpu.monitor.cli import main as cli_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_flight(tmp_path, monkeypatch):
    """Every test gets its own dump dir and a fresh ring; watchdog and
    excepthook are always torn down."""
    monkeypatch.setenv("PADDLE_FLIGHT_DIR", str(tmp_path))
    flight.recorder.clear()
    yield
    flight.stop_watchdog()
    flight.uninstall_excepthook()
    flight.uninstall_signal_handler()
    # uninstall-while-wrapped deliberately retains the original hook
    # so a live chain keeps terminating; between tests the chain is
    # gone, so drop the retained state for full isolation
    flight._orig_excepthook = None
    flight._orig_threading_hook = None
    flight._orig_sig_handler = None
    flight._orig_sig_signum = None


def _wait_for(pred, timeout=10.0, step=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(step)
    return pred()


# ---------------------------------------------------------------------------
# ring
# ---------------------------------------------------------------------------

def test_ring_records_and_drops_oldest():
    rec = flight.FlightRecorder(capacity=16, enabled=True)
    for i in range(40):
        rec.record("ev", i=i)
    t = rec.tail()
    assert len(t) == 16
    assert [e["i"] for e in t] == list(range(24, 40))  # oldest dropped
    assert rec.stats()["dropped"] == 24
    assert rec.tail(3) == t[-3:]


def test_ring_disabled_is_noop():
    rec = flight.FlightRecorder(capacity=16, enabled=False)
    rec.record("ev")
    assert rec.tail() == []


def test_tail_zero_means_none_not_all():
    rec = flight.FlightRecorder(capacity=16, enabled=True)
    for i in range(4):
        rec.record("ev", i=i)
    assert rec.tail(0) == []  # PADDLE_FLIGHT_DUMP_EVENTS=0 -> empty
    assert len(rec.tail(None)) == 4


def test_ring_drop_counter_in_registry(monkeypatch):
    monitor.stat_reset()
    monkeypatch.setattr(flight, "recorder",
                        flight.FlightRecorder(capacity=16, enabled=True))
    for i in range(20):
        flight.record("spin", i=i)
    # registry gauges are amortized on the hot path; any snapshot
    # consumer (exporter/bench/dumps) syncs through this call
    flight.sync_stats()
    assert monitor.stat_get("flight/events") == 20
    assert monitor.stat_get("flight/ring/dropped") == 4


def test_in_flight_registry_begin_end():
    with flight.in_flight("collective", "all_reduce", bytes=256,
                          group="world"):
        entries = flight.inflight_snapshot()
        assert any(e["name"] == "all_reduce"
                   and e["kind"] == "collective" for e in entries)
    assert not any(e["name"] == "all_reduce"
                   for e in flight.inflight_snapshot())
    kinds = [e["kind"] for e in flight.tail()]
    assert "collective_begin" in kinds and "collective_end" in kinds
    endev = [e for e in flight.tail()
             if e["kind"] == "collective_end"][-1]
    assert endev["dur_us"] >= 0


def test_in_flight_cleared_on_exception():
    with pytest.raises(RuntimeError):
        with flight.in_flight("collective", "broadcast"):
            raise RuntimeError("mid-collective")
    assert flight.inflight_snapshot() == []


def test_jit_build_failure_clears_inflight():
    """A to_static function whose first trace raises must not leak
    its in-flight compile entry — the watchdog would report it as a
    permanent hang and it would pollute every later dump's in_flight
    section."""
    from paddle_tpu.jit import to_static

    @to_static
    def f(x):
        raise RuntimeError("build-fail")

    with pytest.raises(RuntimeError, match="build-fail"):
        f(paddle.to_tensor(np.ones((2,), np.float32)))
    assert flight.inflight_snapshot() == []
    kinds = [e["kind"] for e in flight.tail()]
    assert "compile_begin" in kinds and "compile_end" in kinds


def test_collective_flight_event_positional_group():
    """The flight event records the REAL group even when it is passed
    positionally (group sits at a different position per collective) —
    a 'world' mislabel would point the post-mortem at all ranks."""
    import paddle_tpu.distributed as dist

    g = dist.new_group([0])
    t = paddle.to_tensor(np.ones((2, 2), np.float32))
    dist.all_reduce(t, dist.ReduceOp.SUM, g)
    begins = [e for e in flight.tail()
              if e["kind"] == "collective_begin"
              and e["name"] == "all_reduce"]
    assert begins and begins[-1]["group"] == [0]
    assert begins[-1]["bytes"] == 2 * 2 * 4


# ---------------------------------------------------------------------------
# watchdog on a stalled collective
# ---------------------------------------------------------------------------

def test_watchdog_dumps_stalled_collective(tmp_path):
    """Acceptance: a deliberately stalled fake collective (through the
    REAL _instrumented hook) triggers a per-rank dump with all-thread
    stacks, the flight-ring tail and a telemetry snapshot — within the
    timeout, without hanging the suite."""
    from paddle_tpu.distributed import collective as coll

    release = threading.Event()
    entered = threading.Event()

    @coll._instrumented("fake_stall")
    def stalled_collective(tensor=None, group=None):
        entered.set()
        release.wait(30)

    # the watchdog runs its incident hooks once the dump is on disk
    # and counted: an event to wait on, however slow a loaded worker
    # is to write the bundle (a sleep-and-glob poll gave up at times
    # under six workers)
    dumped = threading.Event()

    def on_incident(reason):
        if any(e["name"] == "fake_stall" and e["age_s"] > 0.3
               for e in flight.inflight_snapshot()):
            dumped.set()

    monitor.stat_reset()
    t = threading.Thread(target=stalled_collective, daemon=True,
                         name="stalled-collective")
    flight.add_incident_hook(on_incident)
    wd = flight.start_watchdog(timeout_s=0.3, poll_s=0.05)
    try:
        t.start()
        assert entered.wait(5)
        assert dumped.wait(120), "watchdog wrote no dump"
    finally:
        release.set()
        flight.stop_watchdog()
        flight.remove_incident_hook(on_incident)
        t.join(5)

    # the dump that names the stalled op (an op some earlier test's
    # thread left in flight may have drawn one of its own)
    bundles = [json.load(open(p)) for p in glob.glob(
        str(tmp_path / "watchdog_rank0_*.json"))]
    bundle = next(b for b in bundles if any(
        e["name"] == "fake_stall" for e in b["stuck"]))
    assert bundle["schema"] == flight.DUMP_SCHEMA
    assert bundle["reason"] == "watchdog"
    assert bundle["rank"] == 0 and bundle["pid"] == os.getpid()
    # the stuck op is named, with its age past the timeout
    stuck = bundle["stuck"]
    assert any(e["name"] == "fake_stall"
               and e["kind"] == "collective"
               and e["age_s"] > 0.3 for e in stuck)
    # all-thread stacks include the stalled thread parked in wait()
    stacks = "".join(line for th in bundle["threads"]
                     for line in th["stack"])
    assert "release.wait" in stacks or "stalled_collective" in stacks
    names = {th["name"] for th in bundle["threads"]}
    assert "stalled-collective" in names
    # flight tail shows the collective entering but never exiting
    kinds = [e["kind"] for e in bundle["flight_tail"]]
    assert "collective_begin" in kinds
    begin = next(e for e in bundle["flight_tail"]
                 if e["kind"] == "collective_begin")
    assert begin["name"] == "fake_stall"
    # telemetry snapshot embedded
    assert "stats" in bundle["telemetry"]
    assert wd.fired >= 1
    assert monitor.stat_get("flight/watchdog/fires") >= 1
    assert monitor.stat_get("flight/dumps_written") >= 1


def test_watchdog_reports_each_stuck_op_once():
    tok = flight.begin("collective", "wedged")
    wd = flight.Watchdog(timeout_s=0.01, poll_s=10)
    try:
        now = time.monotonic() + 1  # ages ride the monotonic clock
        assert wd.check(now=now) is not None
        assert wd.check(now=now + 1) is None  # same op: no re-dump
        assert wd.fired == 1
    finally:
        flight.end(tok)
    assert wd.check(now=time.monotonic() + 5) is None  # done: quiet


def test_watchdog_retries_after_failed_dump(monkeypatch):
    """A dump write failing (full disk) must NOT permanently suppress
    the evidence — the op stays unreported and the next poll retries."""
    tok = flight.begin("collective", "wedged-nodisk")
    wd = flight.Watchdog(timeout_s=0.01, poll_s=10)
    calls = []

    def flaky_dump(reason, extra=None, path=None):
        calls.append(reason)
        if len(calls) == 1:
            raise OSError("disk full")
        return "/fake/dump.json"

    monkeypatch.setattr(flight, "write_dump", flaky_dump)
    try:
        now = time.monotonic() + 1
        with pytest.raises(OSError):
            wd.check(now=now)
        assert wd.fired == 0
        assert wd.check(now=now) == "/fake/dump.json"  # retried
        assert wd.fired == 1
    finally:
        flight.end(tok)


def test_watchdog_ignores_fast_ops():
    wd = flight.Watchdog(timeout_s=60, poll_s=10)
    with flight.in_flight("collective", "quick"):
        assert wd.check() is None
    assert wd.fired == 0


# ---------------------------------------------------------------------------
# crash bundles
# ---------------------------------------------------------------------------

def test_excepthook_writes_inspectable_bundle(tmp_path, capsys):
    flight.install_excepthook()
    try:
        try:
            raise ValueError("boom-forensics")
        except ValueError:
            sys.excepthook(*sys.exc_info())
    finally:
        flight.uninstall_excepthook()
    # the original traceback still printed (hook chains, not replaces)
    assert "boom-forensics" in capsys.readouterr().err
    dumps = glob.glob(str(tmp_path / "crash_rank0_*.json"))
    assert len(dumps) == 1
    bundle = json.load(open(dumps[0]))
    assert bundle["reason"] == "crash"
    assert bundle["exception"]["type"] == "ValueError"
    assert "boom-forensics" in bundle["exception"]["message"]
    assert any("boom-forensics" in line
               for line in bundle["exception"]["traceback"])
    # the exception event reached the flight ring
    assert any(e["kind"] == "exception"
               for e in bundle["flight_tail"])
    assert bundle["env"]  # PADDLE_FLIGHT_DIR at minimum
    assert isinstance(bundle["jit_caches"], list)


def test_excepthook_install_idempotent_and_restores():
    orig = sys.excepthook
    flight.install_excepthook()
    flight.install_excepthook()
    assert sys.excepthook is flight._flight_excepthook
    assert flight._orig_excepthook is orig
    flight.uninstall_excepthook()
    assert sys.excepthook is orig


def test_excepthook_no_cycle_when_wrapped_and_rearmed(tmp_path,
                                                      capsys):
    """fit arms; a third-party hook wraps ours; fit arms AGAIN — the
    second install must be a no-op (flag-guarded), or crash-time
    dispatch cycles ours -> wrapper -> ours forever, writing a dump
    per recursion level."""
    flight.install_excepthook()
    inner = sys.excepthook
    calls = []

    def wrapper(etype, value, tb):
        calls.append("wrapper")
        inner(etype, value, tb)

    sys.excepthook = wrapper
    try:
        flight.install_excepthook()  # re-arm (e.g. second fit call)
        try:
            raise ValueError("wrapped-crash")
        except ValueError:
            sys.excepthook(*sys.exc_info())
        # exactly ONE bundle, wrapper ran once, no recursion
        assert len(glob.glob(str(tmp_path / "crash_rank0_*.json"))) \
            == 1
        assert calls == ["wrapper"]
        assert "wrapped-crash" in capsys.readouterr().err
    finally:
        sys.excepthook = wrapper  # fixture's uninstall handles flags
        flight.uninstall_excepthook()
        sys.excepthook = sys.__excepthook__


def test_worker_thread_crash_writes_bundle(tmp_path):
    """An unhandled exception on a WORKER thread routes through
    threading.excepthook, not sys.excepthook — the armed layer must
    still leave a bundle."""
    flight.install_excepthook()
    try:
        def die():
            raise RuntimeError("worker-died")

        t = threading.Thread(target=die, name="doomed-worker",
                             daemon=True)
        t.start()
        t.join(5)
        assert _wait_for(lambda: glob.glob(
            str(tmp_path / "crash_rank0_*.json")), timeout=5)
    finally:
        flight.uninstall_excepthook()
    bundle = json.load(
        open(glob.glob(str(tmp_path / "crash_rank0_*.json"))[0]))
    assert bundle["exception"]["type"] == "RuntimeError"
    assert "worker-died" in bundle["exception"]["message"]


def test_dump_on_crash_context_manager(tmp_path):
    with pytest.raises(RuntimeError):
        with flight.dump_on_crash():
            raise RuntimeError("worker-thread crash")
    dumps = glob.glob(str(tmp_path / "crash_rank0_*.json"))
    assert dumps
    bundle = json.load(open(dumps[0]))
    assert bundle["exception"]["type"] == "RuntimeError"


@pytest.mark.skipif(not hasattr(__import__("signal"), "SIGUSR1"),
                    reason="no SIGUSR1 on this platform")
def test_sigusr1_live_dump_chains_prior_handler(tmp_path):
    import signal as _signal

    seen = []
    prior = lambda s, f: seen.append(s)  # noqa: E731
    old = _signal.signal(_signal.SIGUSR1, prior)
    try:
        assert flight.install_signal_handler()
        os.kill(os.getpid(), _signal.SIGUSR1)
        # the dump runs on a helper thread (the handler itself must
        # not take locks the interrupted frame may hold)
        assert _wait_for(lambda: glob.glob(
            str(tmp_path / "sigusr1_rank0_*.json")), timeout=5)
        # the application's own handler still ran (preemption
        # checkpoint triggers must not be eaten by auto-arm)
        assert seen == [_signal.SIGUSR1]
        flight.uninstall_signal_handler()
        assert _signal.getsignal(_signal.SIGUSR1) is prior
    finally:
        _signal.signal(_signal.SIGUSR1, old)
    bundle = json.load(
        open(glob.glob(str(tmp_path / "sigusr1_rank0_*.json"))[0]))
    assert bundle["reason"] == "sigusr1"
    assert bundle["threads"]


@pytest.mark.skipif(not hasattr(__import__("signal"), "SIGUSR1"),
                    reason="no SIGUSR1 on this platform")
def test_install_signal_handler_one_signal_at_a_time():
    import signal as _signal

    assert flight.install_signal_handler()           # SIGUSR1
    assert flight.install_signal_handler()           # same: still ok
    # a DIFFERENT signal is refused, not silently "succeeded"
    assert flight.install_signal_handler(_signal.SIGUSR2) is False
    assert _signal.getsignal(_signal.SIGUSR2) \
        is not flight._signal_handler
    flight.uninstall_signal_handler()


def test_rank_in_dump_filename(monkeypatch, tmp_path):
    monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
    path = flight.write_dump("manual")
    assert os.path.basename(path).startswith("manual_rank3_")
    assert json.load(open(path))["rank"] == 3


# ---------------------------------------------------------------------------
# arming
# ---------------------------------------------------------------------------

def test_maybe_auto_arm_distributed_default(monkeypatch):
    orig_hook = sys.excepthook
    # single-process, no explicit gate: stays off
    monkeypatch.delenv("PADDLE_FLIGHT_AUTOARM", raising=False)
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
    assert flight.maybe_auto_arm("test") is None
    assert sys.excepthook is orig_hook
    # distributed: on by default
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
    try:
        wd = flight.maybe_auto_arm("test")
        assert wd is not None and wd.running()
        assert sys.excepthook is flight._flight_excepthook
    finally:
        flight.stop_watchdog()
        flight.uninstall_excepthook()
    # explicit off wins even when distributed
    monkeypatch.setenv("PADDLE_FLIGHT_AUTOARM", "0")
    assert flight.maybe_auto_arm("test") is None
    # any non-falsy value forces on (the _env_on contract), even
    # single-process
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
    monkeypatch.setenv("PADDLE_FLIGHT_AUTOARM", "yes")
    try:
        assert flight.maybe_auto_arm("test") is not None
    finally:
        flight.stop_watchdog()
        flight.uninstall_excepthook()


def test_arm_skips_watchdog_when_flight_disabled(monkeypatch):
    """PADDLE_FLIGHT_ENABLE=0: begin() registers nothing, so arm()
    must not spawn a watchdog thread that polls an empty table
    forever; crash dumps still install."""
    orig_hook = sys.excepthook
    monkeypatch.setattr(flight.recorder, "enabled", False)
    try:
        assert flight.arm() is None
        assert flight.get_watchdog() is None
        assert sys.excepthook is flight._flight_excepthook
    finally:
        flight.uninstall_excepthook()
    assert sys.excepthook is orig_hook


def test_fit_auto_arm_gated_on(monkeypatch):
    """Model.fit arms the forensics layer when PADDLE_FLIGHT_AUTOARM=1
    — the same call distributed runs get by default."""
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.hapi import Model
    from paddle_tpu.io import TensorDataset

    monkeypatch.setenv("PADDLE_FLIGHT_AUTOARM", "1")
    net = nn.Linear(4, 2)
    model = Model(net)
    model.prepare(optimizer=optim.SGD(learning_rate=1e-2,
                                      parameters=net.parameters()),
                  loss=nn.MSELoss())
    xs = paddle.to_tensor(np.ones((4, 4), np.float32))
    ys = paddle.to_tensor(np.ones((4, 2), np.float32))
    try:
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=2,
                  verbose=0)
        wd = flight.get_watchdog()
        assert wd is not None and wd.running()
        assert sys.excepthook is flight._flight_excepthook
        assert any(e["kind"] == "auto_arm" and
                   e["where"] == "hapi.Model.fit"
                   for e in flight.tail())
    finally:
        flight.stop_watchdog()
        flight.uninstall_excepthook()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_inspect_json_roundtrip(tmp_path, capsys):
    try:
        raise KeyError("lost-key")
    except KeyError:
        path = flight._crash_dump(*sys.exc_info())
    assert cli_main(["inspect", path, "--json"]) == 0
    out = capsys.readouterr().out
    bundle = json.loads(out)  # machine-readable round trip
    assert bundle["schema"] == flight.DUMP_SCHEMA
    assert bundle["exception"]["type"] == "KeyError"
    # pretty mode renders the same bundle
    assert cli_main(["inspect", path, "--stacks"]) == 0
    pretty = capsys.readouterr().out
    assert "KeyError" in pretty and "flight tail" in pretty


def _fake_trace(path, rank):
    with open(path, "w") as f:
        json.dump({"traceEvents": [
            {"name": "hapi/train_step", "cat": "TrainStep", "ph": "X",
             "ts": 10.0 + rank, "dur": 5.0, "pid": 0, "tid": 7},
            {"name": "fusion", "ph": "X", "ts": 11.0, "dur": 2.0,
             "pid": 1000, "tid": 1},
            {"name": "loss", "ph": "C", "ts": 12.0, "pid": 0,
             "args": {"value": 0.25}},
        ]}, f)


def test_cli_merge_traces(tmp_path, capsys):
    """Acceptance: merge-traces emits ONE chrome trace from >= 2
    per-rank inputs, with disjoint pid spaces and rank labels."""
    p0 = tmp_path / "trace_rank0.json"
    p1 = tmp_path / "trace_rank1.json"
    _fake_trace(p0, 0)
    _fake_trace(p1, 1)
    out = tmp_path / "merged.json"
    assert cli_main(["merge-traces", "-o", str(out),
                     str(p0), str(p1)]) == 0
    merged = json.load(open(out))
    evs = merged["traceEvents"]
    spans = [e for e in evs if e.get("ph") == "X"]
    assert len(spans) == 4  # 2 per rank
    pids = {e["pid"] for e in evs}
    # rank 0 keeps pid 0/1000; rank 1 shifts by the stride
    assert {0, 1000, 100000, 101000} <= pids
    # per-rank events carry their rank in args
    r1 = [e for e in spans if e["pid"] >= 100000]
    assert all(e["args"]["rank"] == 1 for e in r1)
    # Perfetto process labels present
    meta = [e for e in evs if e.get("ph") == "M"
            and e.get("name") == "process_name"]
    labels = {e["args"]["name"] for e in meta}
    assert {"rank0 host", "rank1 host"} <= labels
    assert merged["metadata"]["merged_ranks"] == [0, 1]


def test_cli_merge_traces_rank_from_position(tmp_path):
    """No rankN token in the filename: argument order assigns ranks."""
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    _fake_trace(a, 0)
    _fake_trace(b, 1)
    out = tmp_path / "m.json"
    assert cli_main(["merge-traces", "-o", str(out), str(a),
                     str(b)]) == 0
    assert json.load(open(out))["metadata"]["merged_ranks"] == [0, 1]


def test_cli_merge_traces_rejects_duplicate_ranks(tmp_path, capsys):
    """rank1-from-filename colliding with rank1-from-position must
    refuse rather than silently interleave two ranks' pid spaces."""
    a = tmp_path / "trace_rank1.json"
    b = tmp_path / "other.json"  # position 1 -> also rank 1
    _fake_trace(a, 1)
    _fake_trace(b, 1)
    out = tmp_path / "m.json"
    assert cli_main(["merge-traces", "-o", str(out), str(a),
                     str(b)]) == 2
    assert "duplicate rank" in capsys.readouterr().err
    assert not out.exists()
    # an embedded 'rank' token inside a word is NOT a rank label
    from paddle_tpu.monitor.cli import _rank_of

    assert _rank_of("crank2.json", 7) == 7
    assert _rank_of("metrics_rank3.json", 0) == 3


def test_cli_merge_traces_widens_stride_for_real_pids(tmp_path,
                                                      capsys):
    """An input pid >= the stride (real OS pids) must not bleed into
    the next rank's shifted block — the stride widens automatically."""
    paths = []
    for r in (0, 1):
        p = tmp_path / f"trace_rank{r}.json"
        with open(p, "w") as f:
            json.dump({"traceEvents": [
                {"name": "span", "ph": "X", "ts": 1, "dur": 1,
                 "pid": 123456, "tid": 1}]}, f)
        paths.append(str(p))
    out = tmp_path / "m.json"
    assert cli_main(["merge-traces", "-o", str(out)] + paths) == 0
    assert "widening stride" in capsys.readouterr().err
    merged = json.load(open(out))
    assert merged["metadata"]["pid_stride"] == 1000000
    pids = sorted(e["pid"] for e in merged["traceEvents"]
                  if e.get("ph") == "X")
    assert pids == [123456, 1123456]  # disjoint per-rank blocks


def test_cli_tail_summarizes_exporter_output(tmp_path, capsys):
    from paddle_tpu import monitor as umon

    monitor.stat_reset()
    monitor.stat_add("step/count", 7)
    path = tmp_path / "metrics.jsonl"
    exp = umon.MetricsExporter(str(path), interval=3600)
    exp.flush()
    monitor.stat_add("step/count", 1)
    exp.flush()
    assert cli_main(["tail", str(path)]) == 0
    out = capsys.readouterr().out
    assert "2 flushes" in out
    assert "step/count = 8" in out
    assert cli_main(["tail", str(path), "--all"]) == 0


def test_cli_clean_error_on_bad_input(tmp_path, capsys):
    """Missing or non-JSON inputs print `error: ...` and exit 2 (the
    analysis-CLI contract) instead of dumping a traceback."""
    assert cli_main(["inspect",
                     str(tmp_path / "missing.json")]) == 2
    assert "error:" in capsys.readouterr().err
    bad = tmp_path / "bad.json"
    bad.write_text("{truncated")
    assert cli_main(["inspect", str(bad)]) == 2
    out = tmp_path / "m.json"
    assert cli_main(["merge-traces", "-o", str(out),
                     str(bad)]) == 2
    assert cli_main(["tail", str(tmp_path / "missing.jsonl")]) == 2
    # a hand-filtered bundle with a kind-less tail event still renders
    partial = tmp_path / "partial.json"
    partial.write_text(json.dumps(
        {"reason": "crash", "flight_tail": [{"ts": 1.0}]}))
    assert cli_main(["inspect", str(partial)]) == 0


def test_cli_module_entrypoint():
    """`python -m paddle_tpu.monitor --help` is wired."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.monitor", "--help"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    for sub in ("inspect", "merge-traces", "tail"):
        assert sub in proc.stdout


# ---------------------------------------------------------------------------
# doc drift: every PADDLE_* env var in monitor code is in the README
# ---------------------------------------------------------------------------

def test_jax_ready_probe_attributes_exist():
    """_jax_ready reads private jax attributes; pin them so a jax
    upgrade that moves them fails THIS test instead of silently
    disabling the side-effect-free rank/world probes (which would
    quietly stop auto-arm on jax-native multi-host)."""
    from jax._src import distributed as jdist
    from jax._src import xla_bridge

    assert hasattr(xla_bridge, "_backends")
    assert hasattr(jdist, "global_state")
    from paddle_tpu.distributed.env import _jax_ready

    assert isinstance(_jax_ready(), bool)


def test_cli_merge_traces_preserves_input_process_names(tmp_path):
    """Input traces that already label a pid (XPlane device names)
    keep that label (rank-prefixed) — a synthesized generic label
    would win in viewers that take the last process_name per pid."""
    p = tmp_path / "trace_rank1.json"
    with open(p, "w") as f:
        json.dump({"traceEvents": [
            {"name": "fusion", "ph": "X", "ts": 1, "dur": 1,
             "pid": 1000, "tid": 1},
            {"ph": "M", "name": "process_name", "pid": 1000,
             "args": {"name": "/device:TPU:0"}},
        ]}, f)
    out = tmp_path / "m.json"
    assert cli_main(["merge-traces", "-o", str(out), str(p)]) == 0
    evs = json.load(open(out))["traceEvents"]
    labels = [e["args"]["name"] for e in evs if e.get("ph") == "M"
              and e.get("name") == "process_name"
              and e.get("pid") == 101000]
    assert labels == ["rank1 /device:TPU:0"]


def test_monitor_env_vars_documented_in_readme():
    """CI gate (the test_analysis_selfcheck pattern): every PADDLE_*
    env var the monitor stack — plus the io/jit/hapi performance
    knobs (PADDLE_IO_DEVICE_PREFETCH, PADDLE_JIT_STEPS_PER_DISPATCH)
    and the device/memory surface (monitor/memory.py,
    device/__init__.py: PADDLE_MEM_*) — reads must appear in the
    README env-var table — new knobs can't ship undocumented."""
    files = glob.glob(os.path.join(REPO, "paddle_tpu", "monitor*.py"))
    files += glob.glob(
        os.path.join(REPO, "paddle_tpu", "monitor", "*.py"))
    files += glob.glob(os.path.join(REPO, "paddle_tpu", "io", "*.py"))
    files += glob.glob(os.path.join(REPO, "paddle_tpu", "jit", "*.py"))
    files += glob.glob(os.path.join(REPO, "paddle_tpu", "hapi", "*.py"))
    files += glob.glob(
        os.path.join(REPO, "paddle_tpu", "device", "*.py"))
    # elastic checkpointing (the PADDLE_CKPT_* / EDL env contract)
    files += glob.glob(
        os.path.join(REPO, "paddle_tpu", "incubate", "checkpoint",
                     "*.py"))
    # fused Pallas kernel library + fused optimizer entry
    # (PADDLE_PALLAS_* — ISSUE 8)
    files += glob.glob(
        os.path.join(REPO, "paddle_tpu", "incubate", "nn", "pallas",
                     "*.py"))
    files += glob.glob(
        os.path.join(REPO, "paddle_tpu", "optimizer", "*.py"))
    # sanitizer suite (PADDLE_SANITIZE — ISSUE 10): monitor/sanitize.py
    # is already covered by the monitor glob; extend over analysis/ so
    # static-pass knobs can't ship undocumented either
    files += glob.glob(
        os.path.join(REPO, "paddle_tpu", "analysis", "*.py"))
    assert files, "monitor sources not found"
    pat = re.compile(r"PADDLE_[A-Z0-9_]+")
    used = set()
    for fp in files:
        with open(fp) as f:
            used |= set(pat.findall(f.read()))
    with open(os.path.join(REPO, "README.md")) as f:
        documented = set(pat.findall(f.read()))
    missing = sorted(used - documented)
    assert not missing, (
        f"env vars referenced in paddle_tpu/monitor/ but missing from "
        f"the README table: {missing}")
