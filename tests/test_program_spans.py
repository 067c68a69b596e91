"""Program spans (paddle_tpu.monitor.flight.span): one primitive that
feeds a bounded ring on time.perf_counter() and a jax.profiler
annotation `paddle_tpu/<layer>/<what>`; the train dispatch, the engine
step and every compile leave a fixed span tree; in_flight() and
profiler.RecordEvent go through it; the engine records its programs'
memory footprints and counts a compile-time out-of-memory."""
import glob
import os
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as optim
from paddle_tpu.core import monitor as cmon
from paddle_tpu.monitor import flight

P = flight.SPAN_PREFIX


@pytest.fixture(autouse=True)
def _fresh_ring():
    flight.recorder.clear()
    yield


def _tree(spans, tid=None):
    """[(name, [children...])] by start time, of one thread's spans."""
    spans = [s for s in spans if tid is None or s["tid"] == tid]
    kids = {}
    for s in sorted(spans, key=lambda s: s["start"]):
        kids.setdefault(s["parent"], []).append(s)

    def sub(parent):
        return [(s["name"][len(P):], sub(s["id"]))
                for s in kids.get(parent, [])]

    return sub(0)


def _leaf(*names):
    return [(n, []) for n in names]


# -- the primitive -------------------------------------------------------

def test_nested_spans_record_parent_ids_and_clock():
    import time

    t_before = time.perf_counter()
    with flight.span("train/step", step=7) as outer:
        with flight.span("train/prepare"):
            pass
        inner = flight.span("serve/prefill", req="r1", tokens=5).begin()
        inner.end()
    t_after = time.perf_counter()
    spans = {s["name"]: s for s in flight.spans()}
    step = spans[P + "train/step"]
    assert step["parent"] == 0 and step["ids"] == {"step": 7}
    assert step["id"] == outer.sid
    assert spans[P + "train/prepare"]["parent"] == step["id"]
    pre = spans[P + "serve/prefill"]
    assert pre["parent"] == step["id"]
    assert pre["ids"] == {"req": "r1", "tokens": 5}
    # the ring is on perf_counter, children inside their parent
    assert t_before <= step["start"] <= pre["start"] <= pre["end"] \
        <= step["end"] <= t_after
    assert step["tid"] == threading.get_ident()
    # children close first: the ring is in closing order
    assert [s["name"] for s in flight.spans()][-1] == P + "train/step"


def test_spans_since_and_closed_span():
    import time

    with flight.span("io/a"):
        pass
    cut = time.perf_counter()
    with flight.span("io/b") as b:
        flight.closed_span("compile/x", b.t0, time.perf_counter(),
                           program="x")
    names = [s["name"] for s in flight.spans(since=cut)]
    assert names == [P + "compile/x", P + "io/b"]
    x = flight.spans(since=cut)[0]
    assert x["parent"] == b.sid and x["ids"] == {"program": "x"}


def test_ring_is_bounded_drops_oldest_and_counts(monkeypatch):
    monkeypatch.setattr(flight, "SPAN_CAPACITY", 8)
    monkeypatch.setattr(flight, "recorder",
                        flight.FlightRecorder(capacity=16, enabled=True))
    for i in range(20):
        with flight.span("io/x", i=i):
            pass
    got = flight.spans()
    assert [s["ids"]["i"] for s in got] == list(range(12, 20))
    st = flight.recorder.span_stats()
    assert st == {"closed": 20, "capacity": 8, "dropped": 12}
    flight.sync_stats()
    assert cmon.stat_get("flight/spans") == 20
    assert cmon.stat_get("flight/spans/dropped") == 12


def test_two_threads_keep_their_own_parents():
    ready, go = threading.Barrier(2), threading.Event()
    tids = {}

    def worker(name):
        tids[name] = threading.get_ident()
        with flight.span(f"io/{name}"):
            ready.wait(timeout=10)
            go.wait(timeout=10)
            with flight.span(f"io/{name}/child"):
                pass

    a = threading.Thread(target=worker, args=("a",))
    a.start()
    with flight.span("io/main"):
        ready.wait(timeout=10)
        go.set()
        a.join(timeout=10)
    assert not a.is_alive()
    spans = {s["name"]: s for s in flight.spans()}
    assert spans[P + "io/a"]["parent"] == 0          # not io/main
    assert spans[P + "io/a/child"]["parent"] == spans[P + "io/a"]["id"]
    assert spans[P + "io/a"]["tid"] == tids["a"] \
        != spans[P + "io/main"]["tid"]


def test_off_with_the_flight_ring(monkeypatch):
    monkeypatch.setattr(flight, "recorder",
                        flight.FlightRecorder(capacity=16, enabled=False))
    with flight.span("train/step", step=1) as sp:
        with flight.in_flight("compile", "f"):
            pass
    assert sp.t0 is None
    assert flight.spans() == []
    assert flight.recorder.span_stats()["closed"] == 0


def test_out_of_order_end_keeps_the_ring_record():
    a = flight.begin("collective", "all_reduce", bytes=4)
    b = flight.begin("collective", "broadcast")
    flight.end(a)                       # not the innermost
    flight.end(b)
    with flight.span("io/after"):
        pass
    spans = {s["name"]: s for s in flight.spans()}
    assert {P + "comm/all_reduce", P + "comm/broadcast"} <= set(spans)
    assert spans[P + "comm/all_reduce"]["ids"]["bytes"] == 4
    # and the thread's stack is clean again
    assert spans[P + "io/after"]["parent"] == 0
    assert flight.inflight_snapshot() == []


def test_dump_bundle_carries_the_span_tail(tmp_path, monkeypatch):
    import json

    monkeypatch.setenv("PADDLE_FLIGHT_DIR", str(tmp_path))
    with flight.span("train/step", step=3):
        pass
    bundle = json.load(open(flight.write_dump("sigusr1")))
    tail = bundle["span_tail"]
    assert tail[-1]["name"] == P + "train/step"
    assert tail[-1]["ids"] == {"step": 3}


# -- a wait span: what the host's scheduler did to it ----------------------

HOST_IDS = {"runq_us", "pressure_us"}


@pytest.fixture
def host_files(tmp_path, monkeypatch):
    """A host made of files under tmp_path: write(name, text) puts one
    there; flight looks for all of its accounting there and nowhere
    else, and finds it anew."""
    monkeypatch.setattr(flight, "_SCHEDSTAT", str(tmp_path / "schedstat"))
    monkeypatch.setattr(flight, "_PRESSURE", str(tmp_path / "pressure"))
    monkeypatch.setattr(flight, "_pressure_fd", False)
    monkeypatch.setattr(flight, "_span_tls", threading.local())

    def write(name, text):
        (tmp_path / name).write_text(text)

    return write


def _wait_ids(grow=None):
    with flight.wait_span("serve/decode/wait", step=4):
        if grow is not None:
            grow()
    (rec,) = [s for s in flight.spans()
              if s["name"] == P + "serve/decode/wait"]
    assert rec["ids"]["step"] == 4
    return {k: v for k, v in rec["ids"].items() if k != "step"}


HAS_SCHED = os.path.exists("/proc/thread-self/schedstat")
LINUX = pytest.mark.skipif(not sys.platform.startswith("linux"),
                           reason="the host's accounting is Linux's")


@pytest.mark.skipif(not HAS_SCHED, reason="no schedstat on this host")
def test_a_wait_span_carries_the_threads_own_accounting():
    def burn():
        sum(i * i for i in range(200_000))

    ids = _wait_ids(burn)
    assert "runq_us" in ids and set(ids) <= HOST_IDS
    assert all(isinstance(v, int) and v >= 0 for v in ids.values())
    # on another thread the reading is that thread's own
    got = {}
    t = threading.Thread(target=lambda: got.update(_wait_ids()))
    flight.recorder.clear()
    t.start()
    t.join(timeout=10)
    assert got["runq_us"] >= 0


def test_a_host_without_the_files_gives_no_id_and_raises_nothing(
        host_files):
    """The chip's host (PERF.md section 3): no schedstat, no pressure
    file."""
    assert _wait_ids() == {}
    assert flight._pressure_fd is None


def test_outside_linux_a_wait_span_reads_nothing(host_files, monkeypatch):
    host_files("schedstat", "5000000 7000000 3\n")
    host_files("pressure", "some avg10=0.00 total=10\n")
    monkeypatch.setattr(flight.sys, "platform", "darwin")
    assert _wait_ids() == {}


def test_a_plain_span_reads_no_file(monkeypatch):
    def no_read(*a, **kw):
        raise AssertionError("a plain span read a file")

    monkeypatch.setattr(flight, "_host_reading", no_read)
    monkeypatch.setattr(flight.os, "pread", no_read)
    with flight.span("serve/decode/fetch"):
        with flight.in_flight("collective", "all_reduce"):
            pass
    assert [s["ids"] for s in flight.spans()] == [{}, {}]


@pytest.mark.parametrize("files,grown,want", [
    # the thread's schedstat alone: its second field, nanoseconds in,
    # microseconds out
    ({"schedstat": "5000000 7000000 3\n"},
     {"schedstat": "5250000 7090000 4\n"},
     {"runq_us": 90}),
    # the host's pressure: the `some` line's total, not the `full` one's
    ({"pressure": "some avg10=0.00 avg60=0.00 total=1000\n"
                  "full avg10=0.00 total=7\n"},
     {"pressure": "some avg10=1.50 avg60=0.10 total=91000\n"
                  "full avg10=0.00 total=9\n"},
     {"pressure_us": 90000}),
    # a file that does not read as a number gives no id
    ({"schedstat": "5000000 7000000 3\n", "pressure": "not supported\n"},
     {"schedstat": "5001000 7002000 3\n"},
     {"runq_us": 2}),
])
@LINUX
def test_a_wait_span_keeps_the_growth_of_what_the_host_has(
        host_files, files, grown, want):
    for name, text in files.items():
        host_files(name, text)

    def grow():
        for name, text in grown.items():
            host_files(name, text)

    assert _wait_ids(grow) == want


# -- the sites -----------------------------------------------------------

def _train_step():
    from paddle_tpu.jit import TrainStepCompiler

    paddle.seed(0)
    net = nn.Linear(4, 2)
    opt = optim.Adam(learning_rate=1e-3, parameters=net.parameters())
    step = TrainStepCompiler(net, opt,
                             lambda o, y: ((o - y) ** 2).mean())
    x = paddle.to_tensor(np.ones((3, 4), np.float32))
    y = paddle.to_tensor(np.ones((3, 2), np.float32))
    return step, x, y


def test_train_dispatch_leaves_the_span_tree():
    step, x, y = _train_step()
    for _ in range(3):
        step(x, y)
    first, second, third = _tree(flight.spans())
    prog = "train_step:Linear"
    # the first call compiles, then captures the footprint
    assert first == ("train/step", [
        ("train/prepare", []),
        ("compile/train_step", _leaf("train/prepare", "train/enqueue",
                                     "train/finish")),
        (f"compile/capture/{prog}", [])])
    # the second goes through a second jit cache entry (the fresh opt
    # state's weak types strengthen): its enqueue was a compile
    assert second == ("train/step", [
        ("train/prepare", []), ("train/prepare", []),
        ("train/enqueue", [("compile/train_step", [])]),
        ("train/finish", [])])
    # the steady step
    assert third == ("train/step", _leaf(
        "train/prepare", "train/prepare", "train/enqueue",
        "train/block", "train/finish"))
    steps = [s for s in flight.spans() if s["name"] == P + "train/step"]
    assert [s["ids"]["step"] for s in steps] == [0, 1, 2]
    # the block is a wait span, and the only one of the step
    waits = [s["name"] for s in flight.spans() if HOST_IDS & set(s["ids"])]
    assert set(waits) <= {P + "train/block"}
    if HAS_SCHED:
        assert waits == [P + "train/block"]
    compiles = [s for s in flight.spans()
                if s["name"] == P + "compile/train_step"]
    assert [s["ids"] for s in compiles] == [
        {"program": prog}, {"program": prog, "retrace": 1}]


def _engine(**kw):
    from paddle_tpu.inference.serving import LLMEngine
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, ffn_hidden=128, max_seq_len=64,
                    dropout=0.0, use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    return LLMEngine(model, max_batch=2, block_size=8, num_blocks=32,
                     **kw)


def _sampling(n):
    from paddle_tpu.inference.serving import SamplingParams

    return SamplingParams(max_new_tokens=n)


# where the engine meets the runtime: the inputs' transfer inside the
# enqueue; inside the fetch the next inputs, built beside the device,
# then the wait alone
_ENQUEUE = ("serve/decode/enqueue", [("serve/decode/put", [])])
_FETCH = ("serve/decode/fetch", [("serve/decode/ahead", []),
                                 ("serve/decode/wait", [])])


def test_engine_step_leaves_the_span_tree():
    engine = _engine()
    rid = engine.add_request([1, 2, 3], _sampling(3))
    req = engine.get_request(rid).trace_id
    engine.step()
    engine.step()
    first, second = _tree(flight.spans())
    decode, prefill = "serve_decode:GPTForCausalLM", \
        "serve_prefill:GPTForCausalLM"
    assert first == ("serve/step", [
        ("serve/schedule", [
            ("serve/prefill", [(f"compile/{prefill}", [])]),
            (f"compile/capture/{prefill}", [])]),
        ("serve/decode/prepare", []),
        ("serve/decode", [
            (f"compile/{decode}", [
                ("serve/decode/enqueue", [
                    ("serve/decode/put", []),
                    (f"compile/capture/{decode}", [])])]),
            _FETCH]),
        ("serve/decode/emit", [])])
    assert second == ("serve/step", [
        ("serve/schedule", []),
        ("serve/decode/prepare", []),
        ("serve/decode", [_ENQUEUE, _FETCH]),
        ("serve/decode/emit", [])])
    spans = flight.spans()
    assert [s["ids"]["step"] for s in spans
            if s["name"] == P + "serve/step"] == [1, 2]
    pre = next(s for s in spans if s["name"] == P + "serve/prefill")
    assert pre["ids"] == {"req": req, "padded": 8, "tokens": 3}
    # the wait, and no other span of the engine, is a wait span
    assert {s["name"] for s in spans if HOST_IDS & set(s["ids"])} \
        <= {P + "serve/decode/wait"}
    if HAS_SCHED:
        assert all("runq_us" in s["ids"] for s in spans
                   if s["name"] == P + "serve/decode/wait")
    # the engine's programs count their dispatches as the jit's do
    assert cmon.stat_get(f"jit/{decode}/cache_miss") >= 1
    assert cmon.stat_get(f"jit/{decode}/cache_hit") >= 1
    assert cmon.stat_get(f"jit/{prefill}/cache_miss") >= 1


def test_a_step_that_runs_ahead_encloses_the_next_enqueue():
    """A full batch, `run_ahead=True`: from the second decode on a step
    finds its
    dispatch in flight (no `serve/decode/prepare`, no enqueue of its
    own) and hands over the next between two `serve/decode/fetch`
    spans, side by side, so that a reader that adds up a step's
    children by name counts each second once: fetch > ahead, enqueue
    > put, fetch > wait."""
    engine = _engine(run_ahead=True)
    for prompt in ([1, 2, 3], [4, 5]):
        engine.add_request(prompt, _sampling(6))
    for _ in range(4):
        engine.step()
    steps = _tree(flight.spans())
    assert [name for name, _ in steps[1][1]] == [
        "serve/schedule", "serve/decode/prepare", "serve/decode",
        "serve/decode/emit"]
    ahead = ("serve/decode/fetch", _leaf("serve/decode/ahead"))
    wait = ("serve/decode/fetch", _leaf("serve/decode/wait"))
    assert steps[1][1][2] == ("serve/decode", [
        _ENQUEUE, ahead, _ENQUEUE, wait])
    for step in steps[2:]:
        assert step == ("serve/step", [
            ("serve/schedule", []),
            ("serve/decode", [ahead, _ENQUEUE, wait]),
            ("serve/decode/emit", [])])


def test_a_verify_dispatch_waits_inside_its_fetch():
    engine = _engine(spec_k=3)
    engine.add_request([1, 2, 3], _sampling(6))
    engine.step()
    flight.recorder.clear()
    engine.step()
    by_id = {s["id"]: s for s in flight.spans()}
    (wait,) = [s for s in by_id.values()
               if s["name"] == P + "serve/decode/wait"]
    fetch = by_id[wait["parent"]]
    assert fetch["name"] == P + "serve/decode/fetch"
    assert fetch["start"] <= wait["start"] <= wait["end"] <= fetch["end"]


def test_engine_records_program_memory_and_tpubench_reads_it():
    from tpubench import core

    engine = _engine()
    engine.generate([[1, 2, 3]], sampling=_sampling(2))
    name = "mem/program/serve_decode:GPTForCausalLM/temp_bytes"
    temp = cmon.stat_get(name)
    assert temp > 0
    assert cmon.stat_get(
        "mem/program/serve_prefill:GPTForCausalLM/temp_bytes") > 0
    # tpubench's memory_peak_bytes() globs mem/program/*/temp_bytes
    cell = core.Cell.__new__(core.Cell)
    cell.config, cell.traffic, cell.chips = {}, {}, 1
    run = core.Run(cell, 0, 1.0, 0, "unused", 0.0)
    cmon.stat_set(name, 1 << 40)        # the largest, whatever ran before
    assert run.memory_peak_bytes() >= 1 << 40
    cmon.stat_set(name, temp)


def test_engine_counts_a_compile_time_oom(monkeypatch):
    import jax

    engine = _engine()
    engine.generate([[1, 2, 3]], sampling=_sampling(2))   # warm
    real = engine._enqueue_decode
    calls = {"n": 0}

    def refuse_once(*arrays):
        calls["n"] += 1
        if calls["n"] == 1:
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
                "Ran out of memory in memory space hbm. Used 16.07G of "
                "15.75G hbm.")
        return real(*arrays)

    monkeypatch.setattr(engine, "_enqueue_decode", refuse_once)
    before = (cmon.stat_get("serve/compile_oom"),
              cmon.stat_get("serve/oom_evictions"))
    flight.recorder.clear()
    outs = engine.generate([[1, 2, 3], [4, 5, 6]], sampling=_sampling(3))
    assert [len(o) for o in outs] == [3, 3]
    assert cmon.stat_get("serve/compile_oom") == before[0] + 1
    assert cmon.stat_get("serve/oom_evictions") == before[1] + 1
    evicts = [s for s in flight.spans() if s["name"] == P + "serve/evict"]
    assert len(evicts) == 1
    step = next(s for s in flight.spans() if s["id"] == evicts[0]["parent"])
    assert step["name"] == P + "serve/step"
    # an out-of-memory of the allocator is not the compiler's
    from paddle_tpu.monitor import memory

    runtime = jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "1073741824 bytes.")
    assert memory.is_oom_error(runtime)
    assert not memory.is_compile_oom_error(runtime)


# -- the profiler's clock ------------------------------------------------

def _host_events(trace_dir):
    """{name: [stats dict, ...]} of the `paddle_tpu/` events on the host
    planes of the one trace under `trace_dir`."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(P):
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


def test_profiler_session_holds_the_same_spans(tmp_path):
    import jax

    from paddle_tpu import profiler

    step, x, y = _train_step()
    engine = _engine()
    engine.add_request([1, 2, 3], _sampling(4))
    for _ in range(3):                 # compiles stay out of the session
        step(x, y)
    engine.step()
    flight.recorder.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        step(x, y)
        engine.step()
        with flight.in_flight("collective", "all_reduce", bytes=64):
            pass
        with profiler.RecordEvent("io/fetch_batch", "Dataloader",
                                  args={"batch_size": 4}):
            pass
    finally:
        jax.profiler.stop_trace()
    host = _host_events(str(tmp_path))
    ring = {}
    for s in flight.spans():
        ring.setdefault(s["name"], []).append(s)
    # every span of the ring is in the host plane, as often
    assert {n: len(v) for n, v in host.items()} \
        == {n: len(v) for n, v in ring.items()}
    assert set(host) >= {
        P + n for n in (
            "train/step", "train/prepare", "train/enqueue",
            "train/block", "train/finish", "serve/step",
            "serve/schedule", "serve/decode/prepare", "serve/decode",
            "serve/decode/enqueue", "serve/decode/fetch",
            "serve/decode/emit", "comm/all_reduce", "io/fetch_batch")}
    # ids travel as the event's stats
    assert str(host[P + "train/step"][0]["step"]) == "3"
    assert str(host[P + "comm/all_reduce"][0]["bytes"]) == "64"
    assert str(host[P + "io/fetch_batch"][0]["batch_size"]) == "4"
