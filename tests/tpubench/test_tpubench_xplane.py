"""The reduction from a profiler trace to numbers, on a small trace recorded
on a v5e (tpubench/testdata/probe.xplane.pb: three calls of a jitted matmul +
flash attention forward and backward (dq only), each followed by a jitted copy, under
the spans tpubench/step, tpubench/fetch and tpubench/copy)."""
import os

import pytest

from tpubench import xplane

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PROBE = os.path.join(REPO, "tpubench", "testdata", "probe.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace.from_file(PROBE)


def test_recorded_trace(trace):
    assert list(trace.devices) == [0]
    assert trace.window_seconds() == pytest.approx(0.039187, rel=1e-3)
    assert trace.busy_seconds() == pytest.approx(505.8e-6, rel=1e-3)
    # 3 calls x (forward + dq; nothing asked for dk, dv) flash kernels
    flash = trace.group_events("custom-call:tpu_custom_call")
    assert len(flash) == 6
    ops = trace.op_seconds()
    assert ops["custom-call:tpu_custom_call"] == pytest.approx(
        sum(b - a for a, b in flash))
    assert max(ops, key=ops.get) == "custom-call:tpu_custom_call"
    assert sum(ops.values()) >= trace.busy_seconds()     # ops never overlap
    assert len(trace.module_events("jit_f")) == 2        # first began before
    assert len(trace.module_events("jit__lambda")) == 2  # last ends after
    assert trace.exposed_collective_seconds() is None    # one chip
    names = {s[0] for s in trace.spans}
    assert names == {"tpubench/step", "tpubench/fetch", "tpubench/copy"}
    bd = trace.breakdown()
    assert len(bd["device_ops"]) == 10
    assert bd["device_ops"][0][0] == "custom-call:tpu_custom_call"
    gaps = dict(bd["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        trace.window_seconds() - trace.busy_seconds())


@pytest.mark.parametrize("text,want", [
    ("%fusion.12 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128] %p), "
     "kind=kOutput, calls=%fused_computation.5",
     ("fusion", "fusion", "fusion:kOutput")),
    ("%convert_reduce_fusion = (f32[8,1024]{1,0:T(8,128)S(1)}, f32[]) "
     "fusion(bf16[8,1024,64] %x), kind=kLoop, calls=%fc",
     ("convert_reduce_fusion", "fusion", "convert_reduce_fusion")),
    ('%jvp__.1 = (bf16[8,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[8,1024,8]) '
     'custom-call(bf16[8,1024,64] %b), custom_call_target="tpu_custom_call"',
     ("jvp__", "custom-call", "custom-call:tpu_custom_call")),
    ("%copy.4 = bf16[2,4,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)} copy(bf16[2,4] "
     "%q.1)", ("copy", "copy", "copy")),
    ("%all-reduce-start.3 = f32[1024]{0:T(1024)} all-reduce-start(f32[1024] "
     "%g), replica_groups={}", ("all-reduce-start", "all-reduce-start",
                                "all-reduce-start")),
    ("%while.2 = (s32[], f32[4]) while((s32[], f32[4]) %t), condition=%c, "
     "body=%b", ("while", "while", "while")),
])
def test_parse_op(text, want):
    assert xplane.parse_op(text) == want


def test_interval_arithmetic():
    u = xplane.union([(5, 7), (0, 2), (1, 3), (6, 6.5)])
    assert u == [(0, 3), (5, 7)]
    assert xplane.measure(u) == 5
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 5), (9, 12)]) == \
        [(0, 1), (2, 4), (5, 9)]
    assert xplane.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert xplane.subtract([(0, 2)], []) == [(0, 2)]


def _synthetic():
    ar = "%all-reduce.1 = f32[8] all-reduce(f32[8] %g), replica_groups={}"
    mm = "%fusion.1 = f32[8] fusion(f32[8] %x), kind=kOutput, calls=%f"
    body = "%while = (s32[]) while((s32[]) %t), condition=%c, body=%b"
    dev = {"XLA Ops": [(body, 1.0, 3.0), (mm, 1.0, 2.0), (ar, 1.5, 2.5),
                       (mm, 3.5, 4.0)],
           "XLA Modules": [("jit_step(1)", 1.0, 3.0), ("jit_step(1)", 3.5,
                                                       4.0)]}
    host = {"main": [("tpubench/trace_window", 0.0, 5.0),
                     ("tpubench/train_step", 0.5, 3.2),
                     ("tpubench/next_batch", 3.2, 3.6)]}
    return xplane.Trace({"/device:TPU:0": dev, "/device:TPU:1": dev,
                         "/host:CPU": host})


def test_synthetic_two_chips():
    tr = _synthetic()
    assert tr.window == (0.0, 5.0)
    # the while is a container: its body's ops are traced themselves
    assert tr.busy(0) == [(1.0, 2.5), (3.5, 4.0)]
    assert tr.busy_seconds() == pytest.approx(2.0)
    assert tr.op_seconds() == pytest.approx({"fusion:kOutput": 1.5,
                                             "all-reduce": 1.0})
    # the all-reduce runs 1.5-2.5; compute covers 1.0-2.0: 0.5 s exposed
    assert tr.exposed_collective_seconds() == pytest.approx(0.5)
    assert len(tr.module_events("jit_step", chip=0)) == 2
    tr.devices[0]["modules"] += [("jit__unknown(7)", 0.1, 0.2),
                                 ("jit__unknown(9)", 0.3, 0.4),
                                 ("jit__unknown(9)", 0.5, 0.7)]
    assert len(tr.module_events("jit__unknown", chip=0)) == 3
    assert tr.module_events("jit__unknown", chip=0, most_frequent=True) == \
        [(0.3, 0.4), (0.5, 0.7)]
    assert tr.idle_gaps() == pytest.approx({
        "tpubench/train_step": 1.0 + 1.0,     # 0-1 (mid 0.5) and 2.5-3.5
        xplane.NO_SPAN: 1.0})                 # 4-5
