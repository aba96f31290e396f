"""The readers of the program's own scopes and spans: exact on hand-made
reductions with known scopes and spans, silent on a program without them,
and the instruction-to-scope map on a CPU-compiled scoped function. Last, a
traced tiny run of each kind on the CPU reports every new metric."""
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import pytest

import run as harness
from yardstick import registry, scopes
from yardstick import trace as tr

DATA = registry.BENCH / "tests" / "data"
HLO = """\
ENTRY %main (p: f32[4]) -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%c1, metadata={op_name="jit(step)/adgda.local/jvp(f)/mul" source_file="t.py"}
  %fused_encode_pallas.3 = (u8[2,128]{1,0}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/adgda.gossip/jit(fused_encode_pallas)/pallas_call"}
  %fusion.2 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%c2, metadata={op_name="jit(step)/adgda.gossip/while/body/adgda.local/add"}
  %fusion.4 = f32[] fusion(f32[4]{0} %p), kind=kLoop, calls=%c4, metadata={op_name="jit(step)/adgda.telemetry/reduce_sum"}
  %copy.5 = f32[4]{0} copy(f32[4]{0} %p)
  ROOT %add.6 = f32[4]{0} add(%fusion.2, %copy.5), metadata={op_name="jit(step)/add"}
}
"""


class Run:
    def __init__(self, kind, **kw):
        self.kind = kind
        self.__dict__.update(kw)


def test_scope_of_hand_made_text():
    assert scopes.scope_of(HLO) == {
        "fusion.1": "adgda.local", "fused_encode_pallas.3": "adgda.gossip",
        "fusion.2": "adgda.gossip", "fusion.4": "adgda.telemetry"}


def test_scope_of_a_cpu_compiled_function():
    def f(x):
        with jax.named_scope("adgda.local"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("outer"), jax.named_scope("adgda.gossip"):
            return (y * y.T).sum(0) + 1.0

    hlo = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
    got = scopes.scope_of(hlo)
    assert set(got.values()) == {"adgda.local", "adgda.gossip"}
    assert any(n.startswith("dot") and s == "adgda.local" for n, s in got.items())


def _read(name, run):
    return registry.load_metric(name).read(run)


def test_round_split_on_known_intervals():
    ops = [[("fusion.1", 0, 30), ("fused_encode_pallas.3", 40, 60), ("fusion.2", 60, 70),
            ("fusion.4", 75, 80), ("copy.5", 80, 85), ("unknown.7", 90, 95)]]
    red = tr.Reduction((0, 200), ops, [(tr.WINDOW, 0, 200, {})], [[(35, 100)]])
    run = Run("train", hlo_text=HLO, steps=2, reduction=red)
    # busy: [0,30] + [35,100] = 95 ns; local 30, gossip 30, the rest 35
    assert _read("round_local_ms", run) == pytest.approx(30e-6 / 2)
    assert _read("round_gossip_ms", run) == pytest.approx(30e-6 / 2)
    assert _read("round_other_ms", run) == pytest.approx(35e-6 / 2)
    total = sum(_read(n, run) for n in ("round_local_ms", "round_gossip_ms", "round_other_ms"))
    assert total == pytest.approx(red.busy_ns(0) * 1e-6 / run.steps)


def test_round_split_silent_without_scopes():
    red = tr.Reduction((0, 100), [[("fusion.1", 0, 30)]], [(tr.WINDOW, 0, 100, {})])
    plain = HLO.replace("adgda.", "phase.")
    for name in ("round_local_ms", "round_gossip_ms", "round_other_ms"):
        assert _read(name, Run("train", hlo_text=plain, steps=2, reduction=red)) is None
        assert _read(name, Run("train", hlo_text=None, steps=2, reduction=red)) is None
        assert _read(name, Run("serve", reduction=red)) is None


class Ev:
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns, self.stats = name, start, dur, stats


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _profile():
    """Ticks of 100 ns from 100 on: 0 admits (admit span 100-150, prefill on the
    device 110-140), 1 and 2 decode only (device busy 60 of 100), 3 ends
    outside the window."""
    host = [Ev("engine.step", 100, 100, {"tick": 0, "admitted": 2, "decoded": 1, "built": 0}),
            Ev("engine.admit", 100, 50),
            Ev("engine.prefill", 105, 40, {"bucket": 128, "rows": 2, "bpad": 2}),
            Ev("engine.decode", 150, 10, {"bpad": 4, "occupancy": 3})]
    dev = [Ev("fusion.1", 110, 30), Ev("fusion.2", 155, 40)]
    for k, t in ((1, 200), (2, 300), (3, 400)):
        host += [Ev("engine.step", t, 100, {"tick": k, "admitted": 0, "decoded": 3, "built": 0}),
                 Ev("engine.admit", t, 5), Ev("engine.decode", t + 5, 10),
                 Ev("engine.sample", t + 15, 80), Ev("engine.retire", t + 95, 5)]
        dev.append(Ev("fusion.3", t + 20, 60))
    host.append(Ev("bench.engine_step", 100, 300))

    class PD:
        planes = [Plane("/device:TPU:0", [Line("XLA Ops", dev)]),
                  Plane("/host:CPU", [Line("python3", host + [Ev(tr.WINDOW, 50, 400)])])]

    return PD()


def test_engine_readers_on_known_spans(monkeypatch, tmp_path):
    pd = _profile()
    monkeypatch.setattr(tr, "load", lambda d: pd)
    red = tr.reduce(pd)
    spans = scopes.load_engine_spans(pd)
    assert [n for n, *_ in spans].count("engine.step") == 4
    assert spans[0][3] == {"tick": 0, "admitted": 2, "decoded": 1, "built": 0}
    run = Run("serve", reduction=red)
    assert _read("prefill_device_ms", run) == pytest.approx(30e-6)
    assert _read("decode_tick_idle_ms", run) == pytest.approx(40e-6)  # tick 3 lies outside
    assert len(run.engine_spans) == 16  # loaded once; those that end after 450 left out
    assert red.breakdown() == tr.reduce(pd).breakdown()  # engine spans label no gap


def test_engine_readers_silent_without_spans(monkeypatch, tmp_path):
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)  # no trace there
    red = tr.Reduction((0, 100), [[("fusion.1", 0, 30)]], [(tr.WINDOW, 0, 100, {})])
    for name in ("prefill_device_ms", "decode_tick_idle_ms"):
        assert _read(name, Run("serve", reduction=red)) is None
        assert _read(name, Run("train", reduction=red)) is None


# ------------------------------------------------- traced tiny runs on the CPU
def _cpu_op_lines(plane):
    if plane.name != "/host:CPU":
        return []
    return [ln for ln in plane.lines if ln.name.startswith("tf_XLA")]


def _traced(name, real_cell, monkeypatch, tmp_path):
    """A traced run of the tiny cell ``name`` under the real cell's name (so it
    reports the real cell's metrics) and limits, reduced from the CPU's ops."""
    real = registry.load_cell(real_cell)
    monkeypatch.setattr(registry, "DATA", DATA)
    cell = registry.load_cell(name)
    monkeypatch.setattr(registry, "DATA", registry.BENCH)
    cell = dataclasses.replace(cell, name=real_cell,
                               workload={**cell.workload, "limits": real.limits})
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(tr, "reduce", functools.partial(tr.reduce, op_lines=_cpu_op_lines))
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {"bf16_flops": 1e12,
                                                             "hbm_bytes_per_s": 1e11})
    return harness.run_cell(cell, 2913000131, 2.0, True, jax.devices()[:1], time.perf_counter())


def test_traced_tiny_training_run(monkeypatch, tmp_path):
    res = _traced("tiny-train", "adgda-q17b-ring2-s512", monkeypatch, tmp_path)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"]
    assert m["round_local_ms"] > 0 and m["round_gossip_ms"] > 0 and m["round_other_ms"] >= 0


def test_traced_tiny_serving_run(monkeypatch, tmp_path):
    res = _traced("tiny-serve", "serve-q4b-chat", monkeypatch, tmp_path)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"]
    assert 0 < m["decode_tick_idle_ms"] < m["decode_tick_ms"]
    assert 0 < m["prefill_device_ms"] < m["admit_tick_ms"]
