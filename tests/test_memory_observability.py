"""Memory observability & OOM forensics (paddle_tpu.monitor.memory +
the paddle.device memory-stats API) — the HBM axis of the telemetry
stack: census accounting against known-size arrays, peak/reset
semantics, per-program memory_analysis in jit.cache_report(), a
simulated RESOURCE_EXHAUSTED leaving an "oom" bundle whose memory
section names the top live arrays, and the CLI memory/inspect
round-trip (including pre-memory-schema bundles)."""
import glob
import json
import os

import numpy as np
import pytest

import jax
import paddle_tpu as paddle
from paddle_tpu import device
from paddle_tpu.core import monitor as core_monitor
from paddle_tpu.monitor import flight, memory
from paddle_tpu.monitor.cli import main as cli_main
from jax.errors import JaxRuntimeError

OOM_MSG = ("RESOURCE_EXHAUSTED: Out of memory allocating "
           "1099511627776 bytes (simulated)")


@pytest.fixture(autouse=True)
def _clean(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_FLIGHT_DIR", str(tmp_path))
    flight.recorder.clear()
    yield
    flight.uninstall_excepthook()


# ---------------------------------------------------------------------------
# device stats + census accounting
# ---------------------------------------------------------------------------

def test_memory_allocated_accounts_known_array():
    base = device.memory_allocated()
    a = jax.device_put(np.ones((256, 1024), np.float32))  # 1 MiB
    try:
        assert device.memory_allocated() - base == a.nbytes == 2**20
    finally:
        del a


def test_memory_allocated_resolves_device_specifiers():
    """Reference-API specifiers (int ordinal, "platform:idx" string)
    must read the real device — not silently account 0 bytes against
    a bogus string-keyed watermark."""
    a = jax.device_put(np.ones((64, 64), np.float32))
    try:
        dev = jax.devices()[0]
        want = device.memory_allocated(dev)
        assert device.memory_allocated(0) == want
        assert device.memory_allocated(f"{dev.platform}:0") == want
        assert device.memory_allocated(dev.platform) == want
        with pytest.raises(TypeError):
            device.memory_allocated(True)
    finally:
        del a


def test_census_groups_by_shape_dtype():
    a = jax.device_put(np.ones((128, 64), np.float32))
    b = jax.device_put(np.ones((128, 64), np.float32))
    c = jax.device_put(np.ones((32,), np.int32))
    try:
        census = memory.live_array_census(top_k=0)
        groups = {(tuple(g["shape"]), g["dtype"]): g
                  for g in census["groups"]}
        g = groups[((128, 64), "float32")]
        assert g["count"] >= 2
        assert g["bytes"] >= a.nbytes + b.nbytes
        assert ((32,), "int32") in groups
        assert census["total_bytes"] >= sum(
            gr["bytes"] for gr in census["groups"]) or census["truncated"]
        # grouped report never carries array CONTENTS
        assert "values" not in json.dumps(census)
    finally:
        del a, b, c


def test_census_top_k_truncates_groups_not_totals():
    arrs = [jax.device_put(np.ones((i + 1, 7), np.float32))
            for i in range(5)]
    try:
        full = memory.live_array_census(top_k=0)
        cut = memory.live_array_census(top_k=2)
        assert len(cut["groups"]) <= 2
        assert cut["group_count"] == full["group_count"]
        assert cut["total_bytes"] == full["total_bytes"]
        assert cut["truncated"]
        # ranked by bytes descending
        sizes = [g["bytes"] for g in full["groups"]]
        assert sizes == sorted(sizes, reverse=True)
    finally:
        del arrs


def test_peak_and_reset_semantics():
    a = jax.device_put(np.ones((512, 512), np.float32))  # 1 MiB
    high = device.memory_allocated()
    assert device.max_memory_allocated() >= high
    del a
    low = device.memory_allocated()
    assert low < high
    assert device.max_memory_allocated() >= high  # peak survives free
    new_peak = device.reset_max_memory_allocated()
    assert new_peak == device.memory_allocated()
    assert device.max_memory_allocated() < high


def test_memory_stats_normalized_keys():
    stats = device.memory_stats()
    assert stats["source"] in ("pjrt", "census")
    assert stats["allocated_bytes"] >= 0
    assert stats["peak_bytes"] >= stats["allocated_bytes"]


def test_telemetry_snapshot_syncs_mem_gauges():
    from paddle_tpu import monitor

    a = jax.device_put(np.ones((64, 64), np.float32))
    try:
        snap = monitor.telemetry_snapshot()
        assert snap["stats"]["mem/allocated_bytes"] >= a.nbytes
        assert snap["stats"]["mem/peak_bytes"] >= \
            snap["stats"]["mem/allocated_bytes"] - 1
    finally:
        del a


# ---------------------------------------------------------------------------
# per-program footprints
# ---------------------------------------------------------------------------

def _tiny_step(model_cls=None):
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStepCompiler

    paddle.seed(0)
    net = (model_cls or nn.Linear)(8, 4)
    ce = nn.CrossEntropyLoss()
    opt = optim.Adam(learning_rate=1e-3, parameters=net.parameters())
    step = TrainStepCompiler(net, opt, lambda o, y: ce(o, y))
    x = paddle.to_tensor(np.random.randn(4, 8).astype(np.float32))
    y = paddle.to_tensor(np.random.randint(0, 4, (4,)).astype(np.int64))
    return step, x, y


def test_cache_report_exposes_train_step_memory():
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import cache_report

    # unique class name: gauge + cache_report fn are keyed by
    # type(model).__name__, and other suites also compile Linear steps
    class CacheReportLinear(nn.Linear):
        pass

    step, x, y = _tiny_step(CacheReportLinear)
    step(x, y)
    ent = next(e for e in cache_report()
               if e["kind"] == "train_step"
               and e["fn"] == "CacheReportLinear" and e.get("memory"))
    mem = ent["memory"]
    for key in ("argument_bytes", "output_bytes", "temp_bytes",
                "generated_code_bytes", "total_bytes"):
        assert isinstance(mem[key], int), key
    assert mem["argument_bytes"] > 0  # params + batch are real bytes
    assert core_monitor.stat_get(
        "mem/program/train_step:CacheReportLinear/argument_bytes") \
        == mem["argument_bytes"]


def test_cache_report_exposes_to_static_memory():
    from paddle_tpu.jit import cache_report, to_static

    @to_static
    def poly(v):
        return v * v + v

    poly(paddle.to_tensor(np.ones((16, 16), np.float32)))
    ent = next(e for e in cache_report()
               if e["kind"] == "to_static"
               and e["fn"].split(".")[-1] == "poly")
    assert len(ent["memory"]) == len(ent["keys"])
    mem = ent["memory"][0]
    assert mem and mem["argument_bytes"] >= 16 * 16 * 4


def test_to_static_multi_entry_gauges_not_overwritten():
    """Shape-specialized cache entries of one to_static fn keep
    distinct mem/program gauges — a small tail-batch compile must not
    overwrite the full-batch footprint (last-writer-wins)."""
    from paddle_tpu.jit import to_static

    @to_static
    def poly_entries(v):
        return v * v

    poly_entries(paddle.to_tensor(np.ones((64, 64), np.float32)))
    poly_entries(paddle.to_tensor(np.ones((4, 4), np.float32)))
    fname = poly_entries._telemetry_key
    big = core_monitor.stat_get(f"mem/program/{fname}/argument_bytes")
    small = core_monitor.stat_get(
        f"mem/program/{fname}#1/argument_bytes")
    assert big >= 64 * 64 * 4  # entry 0 (full batch) survives
    assert 0 < small < big  # tail entry landed on its own gauge


def test_program_capture_env_off(monkeypatch):
    from paddle_tpu.jit import cache_report, to_static

    monkeypatch.setenv("PADDLE_MEM_PROGRAM", "0")

    @to_static
    def poly_off(v):
        return v + 1

    poly_off(paddle.to_tensor(np.ones((4, 4), np.float32)))
    ent = next(e for e in cache_report()
               if e["kind"] == "to_static"
               and e["fn"].split(".")[-1] == "poly_off")
    assert ent["memory"] == [None]


def test_program_footprints_sibling_compilers_both_kept():
    """Two live train-step compilers over one model class (the fused
    + tail sibling shape) must not overwrite each other in
    program_footprints()."""
    import gc

    gc.collect()  # drop dead compilers other tests leaked
    base = [n for n in memory.program_footprints()
            if n.startswith("train_step:Linear")]
    step1, x, y = _tiny_step()
    step1(x, y)
    step2, x2, y2 = _tiny_step()
    step2(x2, y2)
    names = [n for n in memory.program_footprints()
             if n.startswith("train_step:Linear")]
    # baseline-relative: earlier suites may hold live Linear
    # compilers of their own — only OUR two must both appear
    assert len(names) == len(base) + 2, (base, names)


def test_cli_inspect_multi_entry_to_static_shows_largest(capsys):
    from paddle_tpu.jit import to_static

    @to_static
    def poly2(v):
        return v * v

    poly2(paddle.to_tensor(np.ones((4, 4), np.float32)))
    poly2(paddle.to_tensor(np.ones((64, 64), np.float32)))  # larger
    path = flight.write_dump("sigusr1")
    assert cli_main(["inspect", path]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines()
                if "to_static:" in ln and "poly2" in ln)
    assert "largest of 2 entries" in line
    assert "arg=16.0KiB" in line  # the 64x64 entry, not the 4x4 one


def test_cost_model_memory_cost_and_cache():
    from paddle_tpu.cost_model import CostModel

    cm = CostModel()

    def f(a, b):
        return a @ b

    x = jax.numpy.ones((64, 64))
    mc = cm.memory_cost(f, x, x)
    assert mc["argument_bytes"] == 2 * 64 * 64 * 4
    assert mc["total_bytes"] > 0
    cm.static_cost(f, x, x)
    cm.profile_measure(f, x, x, warmup=1, iters=2)
    assert len(cm._cache) == 1  # one compile served all three probes
    cm.memory_cost(f, jax.numpy.ones((32, 64)), x)
    assert len(cm._cache) == 2  # new signature, new entry


def test_cost_model_program_cost_reuses_compile():
    """Repeated program_cost probes of one program reuse ONE replay
    closure (and therefore one compiled executable) — a planner loop
    must not pin a fresh executable per call."""
    import paddle_tpu.static as static
    from paddle_tpu.cost_model import CostModel

    paddle.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [8, 16], "float32")
            y = paddle.matmul(x, paddle.to_tensor(
                np.ones((16, 4), np.float32)))
            paddle.nn.functional.relu(y)
        cm = CostModel()
        feed = {"x": np.ones((8, 16), np.float32)}
        cm.program_cost(main, feed)
        cm.program_cost(main, feed)
        assert len(cm._prog_fns) == 1
        assert len(cm._cache) == 1  # second probe was a cache hit
    finally:
        paddle.disable_static()


def test_cost_model_program_cost_evicts_stale_versions():
    """A mutated program (version bump) must not leave the previous
    version's replay closure and compiled executable pinned — the
    planner loop probe/pass/probe pattern would otherwise leak one
    executable per pass iteration."""
    import paddle_tpu.static as static
    from paddle_tpu.cost_model import CostModel

    paddle.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [8, 16], "float32")
            y = paddle.matmul(x, paddle.to_tensor(
                np.ones((16, 4), np.float32)))
            paddle.nn.functional.relu(y)
        cm = CostModel()
        feed = {"x": np.ones((8, 16), np.float32)}
        cm.program_cost(main, feed)
        main._version = getattr(main, "_version", 0) + 1
        cm.program_cost(main, feed)
        assert len(cm._prog_fns) == 1  # stale version evicted
        assert len(cm._cache) == 1  # and its executable with it
    finally:
        paddle.disable_static()


# ---------------------------------------------------------------------------
# step-boundary tracking + chrome-trace counters
# ---------------------------------------------------------------------------

def test_step_timer_records_mem_gauges_and_counters(tmp_path):
    from paddle_tpu import monitor, profiler

    keep = jax.device_put(np.ones((128, 128), np.float32))
    try:
        st = monitor.StepTimer()
        prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
        with prof:
            for _ in range(2):
                st.begin_step()
                st.end_step(batch_size=4)
        assert core_monitor.stat_get("step/mem/allocated_bytes") \
            >= keep.nbytes
        assert core_monitor.stat_get("step/mem/peak_bytes") >= \
            core_monitor.stat_get("step/mem/allocated_bytes")
        trace = tmp_path / "trace_rank0.json"
        prof.export(str(trace))
        evs = json.load(open(trace))["traceEvents"]
        mem_evs = [e for e in evs if e.get("ph") == "C"
                   and e.get("name") == "mem/allocated_bytes"]
        assert mem_evs and all(
            e["args"]["value"] >= keep.nbytes for e in mem_evs)
        # merge-traces keeps the counter series (the Perfetto memory
        # timeline the acceptance criteria names)
        merged = tmp_path / "merged.json"
        assert cli_main(["merge-traces", "-o", str(merged),
                         str(trace)]) == 0
        mevs = json.load(open(merged))["traceEvents"]
        assert any(e.get("ph") == "C"
                   and e.get("name") == "mem/allocated_bytes"
                   for e in mevs)
    finally:
        del keep


def test_step_timer_mem_tracking_env_off(monkeypatch):
    from paddle_tpu import monitor

    monkeypatch.setenv("PADDLE_MEM_STEP", "0")
    core_monitor.stat_reset("step/mem/allocated_bytes")
    st = monitor.StepTimer()
    st.begin_step()
    st.end_step(batch_size=1)
    assert core_monitor.stat_get("step/mem/allocated_bytes") == 0


def test_profiler_step_mem_env_off(tmp_path, monkeypatch):
    """PADDLE_MEM_STEP=0 covers Profiler.step too — same knob, same
    census-walk cost being opted out of."""
    from paddle_tpu import profiler

    monkeypatch.setenv("PADDLE_MEM_STEP", "0")
    keep = jax.device_put(np.ones((64, 64), np.float32))
    try:
        prof = profiler.Profiler(
            targets=[profiler.ProfilerTarget.CPU])
        with prof:
            prof.step(num_samples=4)
        trace = tmp_path / "t.json"
        prof.export(str(trace))
        evs = json.load(open(trace))["traceEvents"]
        assert not [e for e in evs if e.get("ph") == "C"
                    and e.get("name") == "mem/allocated_bytes"]
    finally:
        del keep


# ---------------------------------------------------------------------------
# OOM classification + forensics bundles
# ---------------------------------------------------------------------------

def test_is_oom_error_classification():
    assert memory.is_oom_error(JaxRuntimeError(OOM_MSG))
    assert not memory.is_oom_error(JaxRuntimeError("INTERNAL: boom"))
    assert not memory.is_oom_error(ValueError(OOM_MSG))
    assert not memory.is_oom_error(None)


def test_oom_observer_writes_bundle_with_census(tmp_path):
    held = jax.device_put(np.ones((333, 333), np.float32))
    try:
        with pytest.raises(JaxRuntimeError):
            with memory.oom_observer():
                raise JaxRuntimeError(OOM_MSG)
        paths = glob.glob(str(tmp_path / "oom_*.json"))
        assert len(paths) == 1
        bundle = json.load(open(paths[0]))
        assert bundle["reason"] == "oom"
        assert bundle["exception"]["type"] == "JaxRuntimeError"
        mem = bundle["memory"]
        assert mem["device"]["allocated_bytes"] >= held.nbytes
        assert any(tuple(g["shape"]) == (333, 333)
                   for g in mem["census"]["groups"])
        # per-program footprints ride along (dict, possibly empty)
        assert isinstance(mem["programs"], dict)
        # inspect renders the memory section
        assert cli_main(["inspect", paths[0]]) == 0
    finally:
        del held


def test_excepthook_classifies_oom_reason(tmp_path):
    flight.install_excepthook()
    flight._flight_excepthook(JaxRuntimeError,
                              JaxRuntimeError(OOM_MSG), None)
    assert glob.glob(str(tmp_path / "oom_*.json"))
    assert not glob.glob(str(tmp_path / "crash_*.json"))


def test_excepthook_skips_already_dumped_oom(tmp_path):
    """oom_observer bundles first (census while arrays live); the
    excepthook must not shadow it with a second dump."""
    flight.install_excepthook()
    exc = JaxRuntimeError(OOM_MSG)
    with pytest.raises(JaxRuntimeError):
        with memory.oom_observer():
            raise exc
    flight._flight_excepthook(JaxRuntimeError, exc, None)
    assert len(glob.glob(str(tmp_path / "*_rank*_pid*.json"))) == 1


def test_oom_observer_custom_reason_keeps_census(tmp_path):
    """oom_observer(reason=...) exists to be renamed — the bundle
    must keep the census regardless of the reason string."""
    with pytest.raises(JaxRuntimeError):
        with memory.oom_observer(reason="train_oom"):
            raise JaxRuntimeError(OOM_MSG)
    paths = glob.glob(str(tmp_path / "train_oom_*.json"))
    assert len(paths) == 1
    assert "census" in json.load(open(paths[0]))["memory"]


def test_crash_bundle_carries_light_memory_section(tmp_path):
    """Non-OOM bundles get device stats + program footprints but no
    census (cheap evidence on every dump)."""
    path = flight.write_dump("crash")
    bundle = json.load(open(path))
    mem = bundle["memory"]
    assert "device" in mem and "programs" in mem
    assert "census" not in mem


def test_fit_oom_leaves_bundle(tmp_path, monkeypatch):
    """Model.fit auto-arms oom_observer: a RESOURCE_EXHAUSTED inside
    the train loop leaves an oom bundle and re-raises."""
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.hapi import Model

    paddle.seed(0)
    net = nn.Linear(4, 2)
    m = Model(net)
    m.prepare(optimizer=optim.SGD(learning_rate=0.1,
                                  parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    monkeypatch.setattr(
        Model, "_train_batch_tail",
        lambda self, ins, lbls: (_ for _ in ()).throw(
            JaxRuntimeError(OOM_MSG)))
    x = np.random.randn(8, 4).astype(np.float32)
    y = np.random.randint(0, 2, (8,)).astype(np.int64)
    ds = [(x[i], y[i]) for i in range(8)]
    with pytest.raises(JaxRuntimeError):
        m.fit(ds, batch_size=4, epochs=1, verbose=0)
    paths = glob.glob(str(tmp_path / "oom_*.json"))
    assert len(paths) == 1
    assert "census" in json.load(open(paths[0]))["memory"]


def test_fit_oom_observer_respects_autoarm_off(tmp_path, monkeypatch):
    """PADDLE_FLIGHT_AUTOARM=0 (the flight opt-out maybe_auto_arm
    honors) also disarms fit's OOM observer — no bundle, exception
    still propagates."""
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.hapi import Model

    monkeypatch.setenv("PADDLE_FLIGHT_AUTOARM", "0")
    paddle.seed(0)
    net = nn.Linear(4, 2)
    m = Model(net)
    m.prepare(optimizer=optim.SGD(learning_rate=0.1,
                                  parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    monkeypatch.setattr(
        Model, "_train_batch_tail",
        lambda self, ins, lbls: (_ for _ in ()).throw(
            JaxRuntimeError(OOM_MSG)))
    x = np.random.randn(8, 4).astype(np.float32)
    y = np.random.randint(0, 2, (8,)).astype(np.int64)
    ds = [(x[i], y[i]) for i in range(8)]
    with pytest.raises(JaxRuntimeError):
        m.fit(ds, batch_size=4, epochs=1, verbose=0)
    assert not glob.glob(str(tmp_path / "oom_*.json"))


# ---------------------------------------------------------------------------
# CLI round-trips
# ---------------------------------------------------------------------------

def test_cli_memory_reports_live_process(capsys):
    held = jax.device_put(np.ones((77, 11), np.float32))
    try:
        assert cli_main(["memory"]) == 0
        out = capsys.readouterr().out
        assert "live arrays" in out and "77x11" in out
        assert cli_main(["memory", "--json", "--top", "3"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["device"]["source"] in ("pjrt", "census")
        assert len(rep["census"]["groups"]) <= 3
    finally:
        del held


def test_cli_inspect_tolerates_pre_memory_bundle(tmp_path, capsys):
    """Bundles written before the memory section existed (same
    paddle_tpu.flight/1 schema, key absent) still inspect cleanly."""
    bundle = {"schema": "paddle_tpu.flight/1", "reason": "crash",
              "ts": 1700000000.0, "rank": 0, "world_size": 1,
              "pid": 1234, "host": "h", "argv": [],
              "env": {}, "device": {}, "in_flight": [],
              "threads": [], "flight_tail": [],
              "telemetry": {"stats": {}}, "jit_caches": []}
    p = tmp_path / "crash_rank0_pid1234_1.json"
    with open(p, "w") as f:
        json.dump(bundle, f)
    assert cli_main(["inspect", str(p)]) == 0
    out = capsys.readouterr().out
    assert "flight dump: crash" in out
    assert "memory" not in out.splitlines()[-1]  # no phantom section


def test_cli_inspect_renders_program_memory(tmp_path, capsys):
    from paddle_tpu.jit import cache_report

    step, x, y = _tiny_step()
    step(x, y)
    path = flight.write_dump("sigusr1")
    assert cli_main(["inspect", str(path)]) == 0
    out = capsys.readouterr().out
    assert "memory (" in out
    assert "train_step" in out
    # acceptance: the bundle names per-program temp/argument bytes
    bundle = json.load(open(path))
    mems = [c.get("memory") for c in bundle["jit_caches"]
            if c["kind"] == "train_step"]
    assert any(m and m.get("argument_bytes", 0) > 0 for m in mems)
    assert cache_report()  # still intact after dump


# ---------------------------------------------------------------------------
# device.Event satellite
# ---------------------------------------------------------------------------

def test_event_untimed_does_not_sync_and_errors(monkeypatch):
    calls = []
    monkeypatch.setattr(device, "synchronize",
                        lambda *a, **k: calls.append(1))
    ev = device.Event()  # enable_timing defaults False
    ev.record()
    assert calls == []  # no hard sync for an ordering-only event
    assert ev.query()
    end = device.Event()
    end.record()
    with pytest.raises(RuntimeError, match="enable_timing"):
        ev.elapsed_time(end)


def test_event_timed_measures(monkeypatch):
    calls = []
    monkeypatch.setattr(device, "synchronize",
                        lambda *a, **k: calls.append(1))
    a = device.Event(enable_timing=True)
    b = device.Event(enable_timing=True)
    a.record()
    b.record()
    assert len(calls) == 2  # timed events DO drain the device
    assert a.elapsed_time(b) >= 0.0


def test_event_mixed_timing_errors():
    a = device.Event(enable_timing=True)
    a.record()
    b = device.Event(enable_timing=False)
    b.record()
    with pytest.raises(RuntimeError):
        a.elapsed_time(b)
