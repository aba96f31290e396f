#!/usr/bin/env python3
"""Smoke test of the AD-GDA main path on TPU v5e, through the entry points a
user calls. Weights are random, made from a seed.

  python chip_smoke.py             # one chip: train + serve phases
  python chip_smoke.py --chips 4   # four chips: ppermute gossip vs rolled

One chip:

* train -- ``launch.steps.make_trainer`` + ``DecentralizedTrainer.step`` on
  qwen3-1.7b at its published widths, cut in depth only: 2 nodes on a ring,
  ``kq4b`` compressed gossip through the fused Pallas kernels, 4 x 128
  tokens per node per step. The depth is the deepest candidate whose
  compiled step leaves headroom in HBM (``memory_analysis``). Losses,
  consensus error and the dual weights must stay finite and the step must
  hold the kernels compiled (``tpu_custom_call``). Then one CHOCO round on a
  real-width leaf runs fused and on the ``packed=False`` path: payloads
  bit-equal, ``s`` equal to f32 rounding; the fused encode kernel's payload
  equals the pure-jnp oracle's; the decode-attention kernel agrees with its
  reference at serving widths.
* serve -- a ``ServeEngine`` on the full 28-layer model answers 8 requests;
  every one completes, and the first one's greedy tokens equal the
  ``fastpath=False`` engine's.

Four chips (``--chips 4``): one node per chip on ``make_node_mesh(4)``; the
trainer runs a few steps on the ppermute backend from a node-sharded state,
and one CHOCO round on a real-width leaf must be bit-identical between the
ppermute backend and the rolled oracle (packed and fused payloads).

Times printed are host wall-clock around work that ends in
``block_until_ready``. The last line of stdout is a JSON object naming the
device; the script exits non-zero when any phase fails or when JAX finds no
TPU. It never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.configs import get_config  # noqa: E402
from repro.core import gossip, topology  # noqa: E402
from repro.core.trainer import DecentralizedTrainer  # noqa: E402
from repro.data import node_token_stream  # noqa: E402
from repro.kernels import choco_fused, ops, ref  # noqa: E402
from repro.launch import steps as st  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.launch.mesh import make_node_mesh  # noqa: E402
from repro.launch.sharding import node_shardings  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import Request, ServeEngine  # noqa: E402

ARCH = "qwen3-1.7b"
SEED = 0
BATCH, SEQ = 4, 128  # tokens per node per step: BATCH x SEQ
WARMUP, TIMED = 2, 3  # train steps
HEADROOM = 0.9  # a step may plan for at most this share of HBM
DEFAULT_HBM = int(15.75 * 2**30)  # v5e, when the runtime reports no limit
GAMMA = 0.5
ETA_THETA = 0.05  # launch/train.py's default primal step size
LEAF = (2048, 6144)  # one qwen3-1.7b MLP matrix: the CHOCO parity leaf
SERVE_REQUESTS, SERVE_GEN, SERVE_SLOTS, SERVE_CACHE = 8, 16, 4, 256


def log(msg: str) -> None:
    print(msg, flush=True)


def hbm_limit(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("bytes_limit", DEFAULT_HBM))


def peak_memory(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return gib(peak) if peak is not None else "not reported"


def planned_bytes(compiled) -> int:
    """Per-device bytes the compiled program plans for: arguments, outputs
    and temporaries, less what donation aliases."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


def check(ok, what="") -> None:
    """A smoke check; unlike ``assert`` it also holds under ``python -O``."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _finite(name, x):
    x = np.asarray(x, np.float32)
    check(np.isfinite(x).all(), f"{name} not finite: {x}")
    return x


def _max_abs(a, b):
    return max(
        float(np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64)).max())
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


# -------------------------------------------------------------------- train
def build_step(cfg, nodes, backend, mesh=None):
    """make_trainer + its DecentralizedTrainer.step compiled for the
    abstract state (node-sharded over ``mesh`` when one is given)."""
    trainer = st.make_trainer(
        cfg, nodes, topology="ring", compressor="kq4b", fused_gossip=True,
        eta_theta=ETA_THETA, gossip_backend=backend, mesh=mesh,
    )
    state = st.abstract_trainer_state(trainer, cfg)
    batch = {"tokens": jax.ShapeDtypeStruct((nodes, BATCH, SEQ), jnp.int32)}
    if mesh is not None:
        with_sharding = lambda t: jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            t, node_shardings(t, mesh, nodes),
        )
        state, batch = with_sharding(state), with_sharding(batch)
    t0 = time.perf_counter()
    compiled = DecentralizedTrainer.step.lower(trainer, state, batch).compile()
    return trainer, compiled, time.perf_counter() - t0


def pick_depth(depths, nodes, backend, mesh=None):
    """The deepest candidate cut of qwen3-1.7b whose compiled step plans
    within HEADROOM of one device's HBM: (trainer, compiled, cfg)."""
    cfg0 = get_config(ARCH)
    limit = hbm_limit(jax.devices()[0])
    for depth in depths:
        cfg = dataclasses.replace(cfg0, num_layers=depth)
        trainer, compiled, secs = build_step(cfg, nodes, backend, mesh)
        need = planned_bytes(compiled)
        log(f"train compile: {cfg.name} depth {depth}/{cfg0.num_layers} "
            f"nodes={nodes} backend={backend}: {secs:.1f} s, plans "
            f"{gib(need)} per device of {gib(limit)}")
        if need <= HEADROOM * limit:
            log(f"depth cut: {cfg0.num_layers} -> {depth} layers "
                f"(published widths kept; {gib(need)} <= {HEADROOM:.0%} of HBM)")
            hlo = compiled.as_text()
            check("tpu_custom_call" in hlo, "gossip kernels not compiled")
            log(f"train step holds the Pallas kernels compiled (tpu_custom_call), "
                f"{hlo.count('collective-permute')} collective-permute and "
                f"{hlo.count('all-gather')} all-gather op(s)")
            return trainer, compiled, cfg
        del trainer, compiled
        gc.collect()
    raise SystemExit(f"no depth cut in {depths} fits {HEADROOM:.0%} of {gib(limit)}")


def run_steps(trainer, compiled, cfg, nodes, mesh=None):
    """WARMUP + TIMED steps from a fresh seeded state, built straight into
    the step's placement."""
    state = st.init_trainer_state(
        trainer, cfg, jax.random.PRNGKey(SEED), jax.random.PRNGKey(SEED + 1), mesh
    )
    stream = node_token_stream(nodes, BATCH, SEQ, cfg.vocab_size, seed=SEED)
    times = []
    for i in range(WARMUP + TIMED):
        batch = {"tokens": next(stream)}
        batch = (jax.device_put(batch, node_shardings(batch, mesh, nodes))
                 if mesh is not None else jax.tree.map(jnp.asarray, batch))
        t0 = time.perf_counter()
        state, aux = compiled(state, batch)
        jax.block_until_ready((state, aux))
        times.append(time.perf_counter() - t0)
        loss = _finite("losses", aux["losses"])
        cons = float(_finite("consensus_err", aux["consensus_err"]))
        lam = _finite("lambda", state.lam)
        log(f"step {i}: worst loss {loss.max():.4f}  consensus_err {cons:.3e}  "
            f"lambda_max {lam.max():.4f}  wall {times[-1] * 1e3:.1f} ms"
            + ("  (warm-up)" if i < WARMUP else ""))
    timed = np.array(times[WARMUP:])
    log(f"train step wall (device-synced, {TIMED} steps after {WARMUP} warm-up): "
        f"median {np.median(timed) * 1e3:.1f} ms, min {timed.min() * 1e3:.1f} ms, "
        f"{nodes * BATCH * SEQ} tokens/step")
    log(f"train peak device memory (device 0): {peak_memory(jax.devices()[0])}")


def train_phase():
    run_steps(*pick_depth((4, 3, 2), 2, "rolled"), 2)


# ----------------------------------------------------- CHOCO round parity
def _leaf_state(m):
    k = jax.random.split(jax.random.PRNGKey(SEED), 3)
    theta = {"w": jax.random.normal(k[0], (m, *LEAF))}
    state = gossip.choco_init(theta)._replace(
        theta_hat={"w": 0.5 * jax.random.normal(k[1], (m, *LEAF))},
        s={"w": 0.5 * jax.random.normal(k[2], (m, *LEAF))},
    )
    return theta, state


def choco_parity_phase():
    """One round on a real-width leaf: fused kernels vs the packed=False
    path (payload bit-equal, s to f32 rounding), and the fused encode
    kernel's payload vs the pure-jnp oracle (bit-equal)."""
    m = 2
    topo, comp = topology.ring(m), ops.KernelQuantization(bits=4)
    theta, state = _leaf_state(m)
    key = jax.random.PRNGKey(SEED + 7)
    run = lambda **kw: jax.jit(
        lambda t, s, k: gossip.choco_round(t, s, topo, GAMMA, comp, k, **kw)
    )(theta, state, key)
    t_f, s_f = run(fused=True)
    t_o, s_o = run(packed=False)
    check(_max_abs(t_f, t_o) == 0.0, "theta differs")
    # hat_new = hat + deq(payload): bit-equal hats mean bit-equal payloads
    check(_max_abs(s_f.theta_hat, s_o.theta_hat) == 0.0, "payload differs")
    ds = _max_abs(s_f.s, s_o.s)
    scale = float(jnp.abs(s_o.s["w"]).max())
    check(ds <= 8 * np.finfo(np.float32).eps * scale, f"s differs by {ds}")
    log(f"choco round [{m}, {LEAF[0]}x{LEAF[1]}] f32, fused vs packed=False: "
        f"theta, payload bit-equal; max |ds| = {ds:.3e} (|s| <= {scale:.2f})")

    rows = LEAF[0] * LEAF[1] // ref.LANES
    k = jax.random.split(key, 3)
    tn = jax.random.normal(k[0], (m, rows, ref.LANES))
    hat = 0.5 * jax.random.normal(k[1], (m, rows, ref.LANES))
    xi = jax.random.uniform(k[2], (m, rows, ref.LANES))
    norms = ref.ordered_norm((tn - hat).reshape(m, -1))
    d = rows * ref.LANES
    scales = jnp.stack([16.0 / norms, norms / (16.0 * ref.tau_for(d, 4))], axis=1)
    got = choco_fused.fused_encode_pallas(
        tn, hat, xi, scales, 4, interpret=ops._interpret_default()
    )
    want = jax.jit(lambda *a: ref.fused_encode_ref(*a, 4))(tn, hat, xi, scales)
    for name, g, w in zip(("levels", "signs"), got[:2], want[:2]):
        check(np.array_equal(np.asarray(g), np.asarray(w)), f"{name} differ from oracle")
    dh = _max_abs(got[2], want[2])
    log(f"fused encode kernel vs pure-jnp oracle: packed levels and signs "
        f"bit-equal; max |d hat| = {dh:.3e}")
    check(dh <= 1e-5, dh)


def decode_kernel_phase():
    """The decode-attention kernel at serving widths against its reference."""
    B, L, KV, G, hd = 8, 4096, 8, 2, 128
    k = jax.random.split(jax.random.PRNGKey(SEED + 3), 3)
    q = jax.random.normal(k[0], (B, 1, KV * G, hd), jnp.bfloat16)
    kc = jax.random.normal(k[1], (B, L, KV, hd), jnp.bfloat16)
    vc = jax.random.normal(k[2], (B, L, KV, hd), jnp.bfloat16)
    valid = jnp.arange(L)[None, :] < jnp.arange(1, B + 1)[:, None] * (L // B)
    kq, ks = ref.quantize_kv_ref(kc)
    vq, vs = ref.quantize_kv_ref(vc)
    for name, args, kw in (
        ("bf16", (q, kc, vc, valid), {}),
        ("int8", (q, kq, vq, valid), dict(k_scale=ks, v_scale=vs)),
    ):
        got = jax.jit(lambda *a: ops.decode_attention_kernel(*a, **kw, impl="pallas"))(*args)
        want = jax.jit(lambda *a: ref.decode_attention_ref(
            a[0].reshape(B, KV, G, hd), *a[1:], **kw))(*args)
        err = _max_abs(got.reshape(B, KV, G, hd).astype(jnp.float32),
                       want.astype(jnp.float32))
        log(f"decode kernel {name} [B{B} L{L} KV{KV} G{G} hd{hd}] vs reference: "
            f"max |d| = {err:.3e}")
        check(err < 3e-2, (name, err))


# -------------------------------------------------------------------- serve
def serve_phase():
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = jax.block_until_ready(T.init_model(jax.random.PRNGKey(SEED), cfg))
    log(f"serve: {cfg.name} {cfg.num_layers} layers, random {cfg.dtype} weights "
        f"({time.perf_counter() - t0:.1f} s to build)")
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).tolist()
               for n in rng.integers(8, 96, size=SERVE_REQUESTS)]
    new = lambda: [Request(prompt=p, max_new_tokens=SERVE_GEN) for p in prompts]
    engine = lambda **kw: ServeEngine(cfg, params, max_slots=SERVE_SLOTS,
                                      cache_len=SERVE_CACHE, prompt_bucket=32, **kw)

    t0 = time.perf_counter()
    first = engine().run(new())  # builds every program this traffic needs
    log(f"serve warm-up pass (compiles included): {time.perf_counter() - t0:.1f} s")
    eng, reqs = engine(), new()
    for r in reqs:
        eng.submit(r)
    ticks = []
    while eng.pending or eng.active:
        t0 = time.perf_counter()
        eng.step()  # ends in a host read of the sampled tokens
        ticks.append(time.perf_counter() - t0)
    done = [r for r in reqs if r.done and len(r.output) == SERVE_GEN]
    check(len(done) == SERVE_REQUESTS,
          f"{len(done)}/{SERVE_REQUESTS} requests completed")
    check([r.output for r in reqs] == [r.output for r in first], "runs disagree")
    t = np.array(ticks)
    log(f"serve: {len(done)}/{SERVE_REQUESTS} requests completed in {len(t)} engine "
        f"ticks; tick wall (device-synced) median {np.median(t) * 1e3:.1f} ms, "
        f"max {t.max() * 1e3:.1f} ms")

    legacy = engine(fastpath=False).run(new()[:1])
    check(legacy[0].output == reqs[0].output, (legacy[0].output, reqs[0].output))
    log(f"serve: first request's {SERVE_GEN} greedy tokens equal the "
        f"fastpath=False engine's: {reqs[0].output}")
    log(f"serve peak device memory: {peak_memory(jax.devices()[0])}")


# --------------------------------------------------------------- four chips
def four_chip_phase(nodes=4):
    """One node per chip: the ppermute trainer at published widths, then the
    kernel-payload parity of one CHOCO round against the rolled oracle.

    The rolled oracle runs on one device, as in the multi-device parity grid
    (tests/exchange_parity_main.py): the TPU compiler cannot partition a
    Mosaic kernel over a node-sharded GSPMD layout, so the rolled backend's
    kernels never run on the sharded state."""
    mesh = make_node_mesh(nodes)
    log(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
        f"{nodes // mesh.devices.shape[0]} node(s) per device")
    trainer, compiled, cfg = pick_depth((8, 4, 2), nodes, "ppermute", mesh)
    run_steps(trainer, compiled, cfg, nodes, mesh)
    del trainer, compiled
    gc.collect()

    topo, comp = topology.ring(nodes), ops.KernelQuantization(bits=4)
    theta, state = _leaf_state(nodes)
    sharded = jax.device_put((theta, state), node_shardings((theta, state), mesh, nodes))
    key = jax.random.PRNGKey(SEED + 7)
    for path, kw in (("kq4b-packed", dict(packed=True)), ("kq4b-fused", dict(fused=True))):
        rolled = jax.jit(lambda t, s, k: gossip.choco_round(
            t, s, topo, GAMMA, comp, k, **kw))(theta, state, key)
        spmd = jax.jit(lambda t, s, k: gossip.choco_round(
            t, s, topo, GAMMA, comp, k, backend="ppermute", mesh=mesh, **kw))(*sharded, key)
        diff = _max_abs(jax.device_get(rolled), jax.device_get(spmd))
        log(f"choco round [{nodes}, {LEAF[0]}x{LEAF[1]}] f32 {path}: ppermute "
            f"vs rolled worst |d| = {diff:.3e} (bit-exact required)")
        check(diff == 0.0, (path, diff))


# --------------------------------------------------------------------- main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip ppermute phase")
    args = ap.parse_args()

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform {platform!r}); "
                 "this smoke runs on the chip only")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX finds {len(devices)}")

    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{configure_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase()
    else:
        train_phase()
        gc.collect()
        choco_parity_phase()
        decode_kernel_phase()
        gc.collect()
        serve_phase()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind, "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
