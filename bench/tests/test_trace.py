"""The trace reduction: exact on hand-made intervals, and on a small trace
recorded on the CPU with known op and span names."""
import jax
import jax.numpy as jnp
import pytest

from yardstick import trace as tr


def test_reduction_on_known_intervals():
    ops = [[("fusion.1", 0, 10), ("fused_encode_pallas.1", 5, 20), ("all-gather.2", 30, 45),
            ("fusion.3", 40, 50)]]
    spans = [(tr.WINDOW, 0, 60, {}), ("bench.step", 0, 25, {}), ("bench.sync", 25, 60, {})]
    red = tr.Reduction((0, 60), ops, spans)
    assert red.busy_ns(0) == 20 + 20  # [0,20] and [30,50]
    assert red.window_s == pytest.approx(60e-9)
    assert red.busy_s == pytest.approx(40e-9)
    assert red.gaps(0) == [(20, 30), (50, 60)]
    assert red.busy_ns(0, 8, 35) == 12 + 5
    assert red.span_label(22) == "bench.step"
    assert red.span_label(55) == "bench.sync"
    b = red.breakdown()
    assert b["device_ops"][0] == ["fused_encode_pallas.1", pytest.approx(15e-9)]
    assert dict(map(tuple, b["idle_gaps"])) == {"bench.sync": pytest.approx(20e-9)}


def test_reduce_clips_to_the_window():
    class Ev:
        def __init__(self, name, start, dur, stats=()):
            self.name, self.start_ns, self.duration_ns, self.stats = name, start, dur, stats

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class PD:
        planes = [
            Plane("/device:TPU:0", [Line("XLA Ops", [Ev("a", 0, 100), Ev("b", 150, 100),
                                                     Ev("c", 400, 10)])]),
            Plane("/host:CPU", [Line("python", [Ev(tr.WINDOW, 50, 250), Ev("bench.step", 60, 10)])]),
        ]

    red = tr.reduce(PD())
    assert red.window == (50, 300)
    assert red.ops == [[("a", 50, 100), ("b", 150, 250)]]
    assert red.busy_ns(0) == 150


def _cpu_op_lines(plane):
    if plane.name != "/host:CPU":
        return []
    return [ln for ln in plane.lines if ln.name.startswith("tf_XLA")]


def test_cpu_trace_with_known_names(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tr.start(tmp_path / "t")
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.step", tick=i):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sync"):
                pass
    tr.stop()
    red = tr.reduce(tr.load(tmp_path / "t"), op_lines=_cpu_op_lines)
    assert 0 < red.window_s < 60
    assert [st["tick"] for _, _, st in red.spans_named("bench.step")] == [0, 1, 2]
    dots = sum(n.startswith("dot") for dev in red.ops for n, _, _ in dev)
    assert dots >= 3  # one matmul per call
    assert 0 < red.busy_s <= red.window_s


def test_tpu_op_names():
    assert tr.op_name("%while.16 = (s32[]{:T(128)}, bf16[8,1,1,2560]{3,0,2,1:T(8,128)(2,1)S(1)}) "
                      "while((s32[]{:T(128)}) %tuple.96), condition=%c, body=%b") == ("while.16", "while")
    assert tr.op_name("%fused_encode_pallas.1 = (u8[2,49152,128]{2,1,0:T(8,128)(4,1)S(1)}, "
                      "bf16[2,98304,128]{2,1,0}) custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"") == (
        "fused_encode_pallas.1", "custom-call")
    assert tr.op_name("%copy-start.9 = (bf16[2,4]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) "
                      "copy-start(bf16[2,4]{1,0} %x)") == ("copy-start.9", "copy-start")
    assert tr.op_name("fusion.3") == ("fusion.3", "")


def test_loops_count_as_busy_but_not_as_ops():
    red = tr.Reduction((0, 100), [[("fusion.1", 10, 20), ("all-gather.2", 40, 50)]],
                       [(tr.WINDOW, 0, 100, {})], [[(5, 60)]])
    assert red.busy_ns(0) == 55
    assert sorted(n for n, _ in red.breakdown()["device_ops"]) == ["all-gather.2", "fusion.1"]
