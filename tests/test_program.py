"""jit.Program: the one object that counts, spans, compiles and captures
a jitted function of the package — its own contract at toy size, then
the same contract through each of its callers (`to_static`,
`TrainStepCompiler`, `DistributedTrainStepCompiler`, `LLMEngine` with
both runners), under the names the observability tests pin."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as optim
from paddle_tpu.core import monitor as cmon
from paddle_tpu.jit import Program
from paddle_tpu.jit.program import specialised
from paddle_tpu.monitor import flight

P = flight.SPAN_PREFIX
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_events = []     # every lowering and backend compile of this process
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **kw: _events.append(event))


@pytest.fixture(autouse=True)
def _fresh_ring():
    flight.recorder.clear()
    yield
    assert flight.inflight_snapshot() == []


def _spans(prefix="compile/"):
    return [(s["name"][len(P):], s["ids"]) for s in flight.spans()
            if s["name"].startswith(P + prefix)]


def _compiles():
    return (_events.count(LOWERING), _events.count(BACKEND_COMPILE))


def _matmul(a, b):
    return a @ b + 1.0


def _ones(*shape):
    return jnp.ones(shape, jnp.float32)


def _call(prog, *args):
    """One dispatch the way the package's callers make it."""
    with prog.dispatch():
        return prog.bind(*args)()


# -- (a) the contract ------------------------------------------------------------

def test_first_call_misses_and_spans_later_calls_hit():
    prog = Program(_matmul, "t_prog:first")
    out = _call(prog, _ones(4, 4), _ones(4, 4))
    np.testing.assert_array_equal(np.asarray(out), np.full((4, 4), 5.0))
    assert prog.compiled()
    assert cmon.stat_get("jit/t_prog:first/cache_miss") == 1
    assert cmon.stat_get("jit/t_prog:first/cache_hit") == 0
    assert cmon.stat_get("jit/t_prog:first/compile_us") > 0
    assert _spans() == [("compile/t_prog:first",
                         {"program": "t_prog:first"})]
    kinds = [e["kind"] for e in flight.tail()]
    assert "compile_begin" in kinds and "compile_end" in kinds
    flight.recorder.clear()
    for _ in range(3):
        _call(prog, _ones(4, 4), _ones(4, 4))
        assert not prog.compiled()
    assert cmon.stat_get("jit/t_prog:first/cache_miss") == 1
    assert cmon.stat_get("jit/t_prog:first/cache_hit") == 3
    assert flight.spans() == []          # a warm call opens nothing


def test_a_further_specialisation_is_a_new_miss_named_n():
    """One function at two signatures is two Programs of one family:
    they share its counters, and the second is `<name>#1` wherever a
    name must tell them apart (span id, capture, gauges)."""
    assert specialised("f", 0) == "f" and specialised("f", 2) == "f#2"
    table = {}
    for shape in ((64, 64), (4, 4), (64, 64)):
        prog = table.get(shape)
        if prog is None:
            prog = table[shape] = Program(
                _matmul, specialised("t_prog:spec", len(table)),
                family="t_prog:spec")
        _call(prog, _ones(*shape), _ones(*shape))
        prog.capture()
    assert [p.name for p in table.values()] == ["t_prog:spec",
                                                "t_prog:spec#1"]
    assert cmon.stat_get("jit/t_prog:spec/cache_miss") == 2
    assert cmon.stat_get("jit/t_prog:spec/cache_hit") == 1
    assert _spans() == [
        ("compile/t_prog:spec", {"program": "t_prog:spec"}),
        ("compile/capture/t_prog:spec", {"program": "t_prog:spec"}),
        ("compile/t_prog:spec", {"program": "t_prog:spec#1"}),
        ("compile/capture/t_prog:spec#1", {"program": "t_prog:spec#1"})]
    big = cmon.stat_get("mem/program/t_prog:spec/argument_bytes")
    small = cmon.stat_get("mem/program/t_prog:spec#1/argument_bytes")
    assert big == 2 * 64 * 64 * 4 and small == 2 * 4 * 4 * 4


def test_a_raise_in_the_first_call_closes_the_span_and_leaves_nothing():
    def boom(a):
        raise RuntimeError("trace-fail")

    prog = Program(boom, "t_prog:boom")
    with pytest.raises(RuntimeError, match="trace-fail"):
        _call(prog, _ones(2))
    assert flight.inflight_snapshot() == []
    assert _spans() == [("compile/t_prog:boom", {"program": "t_prog:boom"})]
    prog.capture()                        # nothing ran: nothing to capture
    assert _spans("compile/capture/") == [] and prog.memory is None
    # and it is still the first dispatch that is to come
    with pytest.raises(RuntimeError, match="trace-fail"):
        _call(prog, _ones(2))
    assert cmon.stat_get("jit/t_prog:boom/cache_miss") == 2
    assert cmon.stat_get("jit/t_prog:boom/cache_hit") == 0


def test_capture_reads_memory_and_cost_off_the_calls_own_compile():
    prog = Program(_matmul, "t_prog:cap")
    _call(prog, _ones(32, 32), _ones(32, 32))
    before = _compiles()
    prog.capture()
    # the capture lowered and compiled nothing anew: its avals are the
    # call's, so it finds the executable the call made
    assert _compiles() == before
    assert _spans("compile/capture/") == [
        ("compile/capture/t_prog:cap", {"program": "t_prog:cap"})]
    assert prog.memory["argument_bytes"] == 2 * 32 * 32 * 4
    assert "mem/program/t_prog:cap/temp_bytes" in cmon.registry.snapshot()
    assert cmon.stat_get("mem/program/t_prog:cap/argument_bytes") \
        == prog.memory["argument_bytes"]
    assert prog.cost["flops"] >= 2 * 32 * 32 * 32
    assert cmon.stat_get("perf/program/t_prog:cap/flops") \
        == prog.cost["flops"]
    assert cmon.stat_get("jit/t_prog:cap/mem_capture_us") > 0
    flight.recorder.clear()
    _call(prog, _ones(32, 32), _ones(32, 32))
    prog.capture()                        # once: a warm call has none
    assert flight.spans() == []


@pytest.mark.parametrize("off,kept", [
    (("PADDLE_MEM_PROGRAM", "PADDLE_PERF_PROGRAM"), ()),
    (("PADDLE_MEM_PROGRAM",), ("cost",)),
    (("PADDLE_PERF_PROGRAM",), ("memory",))])
def test_capture_options(monkeypatch, off, kept):
    """Both off: no capture span and no second lower or compile; one
    off: the other's record alone, from the one compiled object."""
    for name in off:
        monkeypatch.setenv(name, "0")
    name = "t_prog:opt" + "".join(k[0] for k in kept)
    prog = Program(_matmul, name)
    _call(prog, _ones(8, 8), _ones(8, 8))
    before = _compiles()
    prog.capture()
    assert _compiles() == before
    assert len(_spans("compile/capture/")) == (1 if kept else 0)
    assert (prog.memory is not None) == ("memory" in kept)
    assert (prog.cost is not None) == ("cost" in kept)
    gauges = [k for k in cmon.registry.snapshot()
              if k.startswith((f"mem/program/{name}/",
                               f"perf/program/{name}/"))]
    assert {g.split("/")[0] for g in gauges} \
        == {{"memory": "mem", "cost": "perf"}[k] for k in kept}


@pytest.mark.parametrize("backend,off,want", [
    ("tpu", (), 1), ("tpu", ("PADDLE_MEM_PROGRAM", "PADDLE_PERF_PROGRAM"), 1),
    ("cpu", (), 0)])
def test_a_first_dispatch_ends_with_one_full_collection(
        monkeypatch, backend, off, want):
    """capture() after a first call collects the garbage a trace and
    a lowering left, once, on an accelerator, whether or not a
    footprint was asked for; never on the CPU, never after a warm
    call, and not where the first call raised."""
    from paddle_tpu.jit import program as pg

    for name in off:
        monkeypatch.setenv(name, "0")
    monkeypatch.setattr(pg.jax, "default_backend", lambda: backend)
    monkeypatch.setattr(pg, "arm_compile_cache", lambda: None)
    full = []
    monkeypatch.setattr(pg.gc, "collect", lambda *a: full.append(a))
    prog = Program(_matmul, f"t_prog:gc:{backend}:{len(off)}")
    with pytest.raises(TypeError):
        _call(prog, _ones(8, 8), _ones(4, 4))
    prog.capture()
    assert full == []
    _call(prog, _ones(8, 8), _ones(8, 8))
    assert full == []                    # the caller places it
    prog.capture()
    assert full == [()] * want
    _call(prog, _ones(8, 8), _ones(8, 8))
    prog.capture()
    assert full == [()] * want


def test_a_donated_argument_is_not_touched_by_the_capture():
    def bump(pool, x):
        return pool.at[0].add(x)

    prog = Program(bump, "t_prog:donate", donate_argnums=(0,))
    pool = jnp.zeros((16, 8))
    out = _call(prog, pool, _ones(8))
    assert pool.is_deleted()             # the call consumed it ...
    prog.capture()                       # ... and the capture needs only
    assert prog.memory is not None       # what it was, not what it held
    assert prog.memory["argument_bytes"] == (16 * 8 + 8) * 4
    assert float(out[0, 0]) == 1.0 and not out.is_deleted()


def test_program_under_an_outer_trace_inlines():
    """jax.grad / an outer jit trace through a Program as through the
    jitted function it is (the differentiable to_static path), on its
    first call as on a warm one."""
    prog = Program(lambda w, x: jnp.sum((x @ w) ** 2), "t_prog:grad")
    w, x = _ones(3, 2), _ones(4, 3)
    want = jax.grad(lambda w: jnp.sum((x @ w) ** 2))(w)
    cold = jax.grad(lambda w: _call(prog, w, x))(w)   # first: tracers
    prog.capture()
    assert prog.memory["argument_bytes"] == (3 * 2 + 4 * 3) * 4
    warm = jax.jit(jax.grad(lambda w: _call(prog, w, x)))(w)
    np.testing.assert_allclose(np.asarray(cold), np.asarray(want))
    np.testing.assert_allclose(np.asarray(warm), np.asarray(want))
    assert float(_call(prog, w, x)) == 4 * 2 * 9.0    # and concretely
    assert cmon.stat_get("jit/t_prog:grad/cache_miss") == 1


def test_a_later_call_that_compiles_is_a_retrace_in_the_ring():
    # (two jits of one function object share its trace cache: a
    # function of this test's own)
    prog = Program(lambda a, b: a @ b + 1.0, "t_prog:retrace")
    _call(prog, _ones(4, 4), _ones(4, 4))
    assert prog.compiled()
    flight.recorder.clear()
    with prog.dispatch(), flight.span("train/enqueue"):
        prog.bind(_ones(6, 6), _ones(6, 6))()     # a new batch shape
        assert prog.compiled()                    # not a dispatch sample
        assert not prog.compiled()                # asked once a call
    retrace, enqueue = flight.spans()             # in closing order
    assert enqueue["name"] == P + "train/enqueue"
    assert retrace["name"] == P + "compile/t_prog:retrace"
    assert retrace["ids"] == {"program": "t_prog:retrace", "retrace": 1}
    assert retrace["parent"] == enqueue["id"]
    assert cmon.stat_get("jit/t_prog:retrace/cache_hit") == 1
    _call(prog, _ones(6, 6), _ones(6, 6))
    assert not prog.compiled()
    assert prog.cache_size() == 2
    assert prog.lower(_ones(4, 4), _ones(4, 4)).compile() is not None


def test_dispatch_spans_the_callers_block_and_counts_once():
    prog = Program(_matmul, "t_prog:scope")
    for _ in range(2):
        with prog.dispatch():
            with flight.span("train/prepare"):
                pass
            prog.bind(_ones(4, 4), _ones(4, 4))()
            prog.bind(_ones(4, 4), _ones(4, 4))()    # e.g. k draft steps
    assert cmon.stat_get("jit/t_prog:scope/cache_miss") == 1
    assert cmon.stat_get("jit/t_prog:scope/cache_hit") == 1
    by_name = {s["name"][len(P):]: s for s in flight.spans()}
    compile_, prepare = by_name["compile/t_prog:scope"], \
        [s for s in flight.spans() if s["name"] == P + "train/prepare"]
    assert prepare[0]["parent"] == compile_["id"]    # the first: inside
    assert prepare[1]["parent"] == 0                 # the second: alone


def test_the_call_reaches_jit_from_the_callers_own_frame():
    """No frame of the Program's lies between the caller and jax.jit
    while the function is traced: two such frames made the lowering
    of each of the engine's programs 0.2-0.5 s slower on the chip
    (PERF.md section 6, PR 29)."""
    import traceback

    seen = []

    def fn(a):
        seen.append([f.filename for f in traceback.extract_stack()])
        return a + 1

    prog = Program(fn, "t_prog:frames")
    with prog.dispatch():
        prog.bind(_ones(2))()
    (files,) = seen
    assert files.count(__file__) == 2          # this test, and fn
    assert not [f for f in files if f.endswith("jit/program.py")]


def test_a_failed_capture_never_fails_the_caller(monkeypatch):
    from paddle_tpu.monitor import memory

    def refuse(name, compiled):
        raise RuntimeError("no analysis on this backend")

    monkeypatch.setattr(memory, "record_program_memory", refuse)
    prog = Program(_matmul, "t_prog:nocap")
    out = _call(prog, _ones(4, 4), _ones(4, 4))
    prog.capture()
    assert prog.memory is None and float(out[0, 0]) == 5.0
    assert flight.inflight_snapshot() == []


# -- (b) the same contract through each caller -----------------------------------

def _mse(o, y):
    return ((o - y) ** 2).mean()


def _to_static():
    from paddle_tpu.jit import to_static

    @to_static
    def t_prog_caller(v):
        return v @ v + v

    x = paddle.to_tensor(np.ones((8, 8), np.float32))
    fname = t_prog_caller._telemetry_key
    return (lambda: t_prog_caller(x)), [(fname, fname)]


def _train_step(distributed):
    from paddle_tpu.jit import TrainStepCompiler

    class TProgNet(nn.Linear):
        pass

    paddle.seed(0)
    net = TProgNet(4, 2)
    opt = optim.SGD(learning_rate=0.1, parameters=net.parameters())
    if distributed:
        from paddle_tpu.distributed.mesh import build_mesh, set_mesh
        from paddle_tpu.jit.distributed import \
            DistributedTrainStepCompiler

        mesh = build_mesh({"dp": 2}, devices=jax.devices()[:2])
        set_mesh(mesh)
        step = DistributedTrainStepCompiler(net, opt, loss_fn=_mse,
                                            mesh=mesh)
    else:
        step = TrainStepCompiler(net, opt, _mse)
    x = paddle.to_tensor(np.ones((4, 4), np.float32))
    y = paddle.to_tensor(np.ones((4, 2), np.float32))
    return (lambda: step(x, y)), [("train_step", "train_step:TProgNet")]


def _engine(glm):
    from paddle_tpu.inference.serving import LLMEngine, SamplingParams

    paddle.seed(0)
    if glm:
        from test_glm4_moe_lite import TOY

        from paddle_tpu.text.models import glm4_moe_lite

        model = glm4_moe_lite.Glm4MoeLiteForCausalLM(
            glm4_moe_lite.Glm4MoeLiteConfig(**TOY))
    else:
        from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

        model = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
            ffn_hidden=64, max_seq_len=32, dropout=0.0,
            use_flash_attention=False))
    model.eval()
    engine = LLMEngine(model, max_batch=4, block_size=4, num_blocks=32,
                       max_seq_len=32)

    def dispatch():
        # a request in the one prefill bucket, then an engine step: a
        # prefill and a decode dispatch
        engine.add_request([1, 2, 3], SamplingParams(max_new_tokens=4))
        engine.step()

    cls = type(model).__name__
    return dispatch, [(f"serve_prefill:{cls}",) * 2,
                      (f"serve_decode:{cls}",) * 2]


@pytest.mark.parametrize("make", [
    _to_static,
    lambda: _train_step(distributed=False),
    lambda: _train_step(distributed=True),
    lambda: _engine(glm=False),
    lambda: _engine(glm=True),
], ids=["to_static", "train_step", "train_step_dp2", "engine_gpt2",
        "engine_glm"])
def test_callers_keep_the_contract_and_the_pinned_names(make):
    dispatch, programs = make()
    keys = [f"jit/{fam}/{c}" for fam, _ in programs
            for c in ("cache_miss", "cache_hit", "compile_us",
                      "mem_capture_us")]
    before = {k: cmon.stat_get(k) for k in keys}
    try:
        dispatch()
        first = {k: cmon.stat_get(k) - before[k] for k in keys}
        spans = _spans()
        for fam, name in programs:
            assert first[f"jit/{fam}/cache_miss"] == 1
            assert first[f"jit/{fam}/cache_hit"] == 0
            assert first[f"jit/{fam}/compile_us"] > 0
            assert first[f"jit/{fam}/mem_capture_us"] > 0
            assert spans.count((f"compile/{fam}", {"program": name})) == 1
            assert spans.count((f"compile/capture/{name}",
                                {"program": name})) == 1
            assert f"mem/program/{name}/temp_bytes" \
                in cmon.registry.snapshot()
            assert cmon.stat_get(f"mem/program/{name}/argument_bytes") > 0
            assert cmon.stat_get(f"perf/program/{name}/flops") > 0
        flight.recorder.clear()
        compiled = _compiles()
        dispatch()
        dispatch()
        for fam, name in programs:
            assert cmon.stat_get(f"jit/{fam}/cache_miss") \
                - before[f"jit/{fam}/cache_miss"] == 1
            assert cmon.stat_get(f"jit/{fam}/cache_hit") \
                - before[f"jit/{fam}/cache_hit"] == 2
        # warm dispatches: no capture, and no compile span but a
        # retrace's (the train step's second call, whose fresh
        # optimizer state's weak types strengthen)
        assert _spans("compile/capture/") == []
        assert all(ids.get("retrace") == 1 for _, ids in _spans())
        if not _spans():
            assert _compiles()[1] == compiled[1]
    finally:
        from paddle_tpu.distributed.mesh import set_mesh

        set_mesh(None)
