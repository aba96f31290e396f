"""The program's own observability: named scopes on the phases of the AD-GDA
round, and profiler host spans in the serving tick.

Scopes change only the ``op_name`` metadata of the compiled round (the same
instructions, fusions and kernel names with or without them); every Pallas
gossip kernel sits under ``adgda.gossip``. Under a CPU profiler trace the
engine writes one ``engine.step`` span per tick whose stats match the tick,
with its phases nested inside. The wall TTFT is stamped once the first token
is on the host, after the prefill.
"""
import contextlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ADGDAConfig, adgda_trainer
from repro.models import transformer as T
from repro.serving import Request, ServeEngine

M = 2
OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE = re.compile(r"(?:^|/)(adgda\.[a-z]+)")
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=")


def _loss(theta, batch, key):
    h = jnp.tanh(batch["x"] @ theta["w1"])
    return jnp.mean((h @ theta["w2"] - batch["y"]) ** 2)


def _compiled_round(fused: bool) -> str:
    cfg = ADGDAConfig(num_nodes=M, topology="ring", compressor="kq4b", fused_gossip=fused,
                      alpha=0.05, eta_theta=0.05, eta_lambda=0.05)
    tr = adgda_trainer(cfg, _loss)
    state = tr.init({"w1": jnp.full((16, 32), 0.1), "w2": jnp.full((32, 4), 0.1)},
                    jax.random.PRNGKey(0))
    batch = {"x": jnp.ones((M, 8, 16)), "y": jnp.zeros((M, 8, 4))}
    return type(tr).step.lower(tr, state, batch).compile().as_text()


def _scopes(hlo: str) -> list[tuple[str, str | None]]:
    """(op_name, first adgda scope) of every instruction with an op_name."""
    out = []
    for line in hlo.splitlines():
        op = OP_NAME.search(line) if INSTR.match(line) else None
        if op:
            sc = SCOPE.search(op.group(1))
            out.append((op.group(1), sc.group(1) if sc else None))
    return out


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "packed"])
def test_round_phases_are_scoped(fused):
    ops = _scopes(_compiled_round(fused))
    scopes = {sc for _, sc in ops}
    assert {"adgda.local", "adgda.gossip", "adgda.dual", "adgda.telemetry"} <= scopes
    kernels = [(name, sc) for name, sc in ops if "_pallas" in name]
    names = {"fused_encode_pallas", "fused_mix_pallas"} if fused else {
        "quantize_pallas", "dequantize_pallas"}
    assert {k for k in names if any(k in n for n, _ in kernels)} == names
    assert {sc for _, sc in kernels} == {"adgda.gossip"}


def _instructions(hlo: str) -> list[str]:
    return [re.sub(r", (metadata=\{[^}]*\}|stack_frame_id=\d+)", "", line)
            for line in hlo.splitlines() if INSTR.match(line)]


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "packed"])
def test_scopes_change_only_metadata(fused, monkeypatch):
    scoped = _compiled_round(fused)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _compiled_round(fused)
    assert "adgda." in scoped and "adgda." not in plain
    assert _instructions(scoped) == _instructions(plain)


# ------------------------------------------------------------ serving spans
@pytest.fixture(scope="module")
def engine_setup():
    cfg = get_config("qwen3-1.7b").reduced()
    return cfg, T.init_model(jax.random.PRNGKey(0), cfg)


def _engine_spans(path):
    files = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for ln in plane.lines for ev in ln.events if ev.name.startswith("engine.")]
    return sorted(spans, key=lambda s: s[1])


@pytest.mark.parametrize("fastpath", [True, False], ids=["fast", "legacy"])
def test_engine_tick_spans(engine_setup, tmp_path, fastpath):
    cfg, params = engine_setup
    engine = ServeEngine(cfg, params, max_slots=2, cache_len=64, prompt_bucket=8,
                         fastpath=fastpath)
    rng = np.random.default_rng(0)
    for n in (5, 9, 12):
        engine.submit(Request(prompt=rng.integers(1, cfg.vocab_size, n).tolist(),
                              max_new_tokens=3))
    engine.step()  # admits two requests and builds their programs, untraced
    ticks, built = [], engine._built()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        while engine.pending or engine.active:
            tick, pending, tokens = engine._steps, len(engine.pending), engine.tokens_generated
            engine.step()
            admitted = pending - len(engine.pending)
            ticks.append((tick, admitted, engine.tokens_generated - tokens - admitted))
    finally:
        jax.profiler.stop_trace()
    spans = _engine_spans(tmp_path)
    steps = [sp for sp in spans if sp[0] == "engine.step"]
    assert [(st["tick"], st["admitted"], st["decoded"]) for *_, st in steps] == ticks
    assert sum(a for _, a, _ in ticks) == 1  # the third request
    # the third prompt's exact-length prefill is a new program
    assert sum(st["built"] for *_, st in steps) == engine._built() - built > 0
    children = [sp for sp in spans if sp[0] != "engine.step"]
    if not fastpath:
        assert children == []
        return
    for name, s, e, st in children:  # every phase lies inside a tick
        assert any(s0 <= s and e <= e0 for _, s0, e0, _ in steps), name
    for _, s0, e0, st in steps:
        inner = [sp for sp in children if s0 <= sp[1] and sp[2] <= e0]
        kinds = [sp[0] for sp in inner]
        assert kinds.count("engine.admit") == 1
        assert kinds.count("engine.prefill") == (1 if st["admitted"] else 0)
        if st["decoded"]:
            dec = next(sp for sp in inner if sp[0] == "engine.decode")
            assert dec[3]["occupancy"] == st["decoded"]
            assert kinds.count("engine.sample") == kinds.count("engine.retire") == 1
    prefill = next(sp for sp in children if sp[0] == "engine.prefill")
    assert prefill[3]["rows"] == 1 and prefill[3]["bpad"] == 1
    assert prefill[3]["bucket"] >= 12


@pytest.mark.parametrize("fastpath", [True, False], ids=["fast", "legacy"])
def test_first_token_wall_is_stamped_after_the_prefill(engine_setup, monkeypatch, fastpath):
    cfg, params = engine_setup
    engine = ServeEngine(cfg, params, max_slots=2, cache_len=64, prompt_bucket=8,
                         fastpath=fastpath)
    done = []

    def timed(fn):
        def run(*args):
            out = jax.block_until_ready(fn(*args))
            done.append(time.time())
            return out
        return run

    if fastpath:
        program = engine._program
        monkeypatch.setattr(engine, "_program", lambda kind, *a, **k: (
            timed(program(kind, *a, **k)) if kind == "prefill" else program(kind, *a, **k)))
    else:
        prefill_fn = engine._prefill_fn
        monkeypatch.setattr(engine, "_prefill_fn", lambda n: timed(prefill_fn(n)))
    req = Request(prompt=[3, 1, 4, 1, 5], max_new_tokens=2)
    engine.submit(req)
    engine.step()
    assert len(done) == 1 and req.first_wall >= done[0]
    assert req.ttft_ticks == 0  # tick TTFT is queue wait, unchanged
