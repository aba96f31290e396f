"""Training cells: AD-GDA rounds (``DecentralizedTrainer.step`` as built by
``launch.steps.make_trainer``) on seeded weights and seeded token batches.

Set-up builds one object, the compiled step with its state, and drives it
through its first rounds with the window's own call and feed; the window then
keeps calling that same object for ``seconds``. Nothing compiles in the window.
The host syncs at the window's edges and keeps at most two rounds in flight
(it waits on the losses of the round before last), so the device is never
starved and the host never runs ahead. After the window, with the program's
state freed, the plain reference recomputes the first rounds and the
comparison decides ``correct``: the losses, the change of theta, and the CHOCO
state (hat and s) after the first and the last of them.
"""
from __future__ import annotations

import collections
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from kinds.program import model_config
from repro.core.trainer import DecentralizedTrainer
from repro.launch import steps as st
from yardstick import adgda_ref, compare, loadgen, weights
from yardstick import trace as tr
from yardstick.device import memory_peak_bytes

TRACE_S = 8.0  # a traced run measures a short window of its own
FEED = 8  # distinct batches drawn from the seed; the window cycles them
PROBE = 3  # first rounds the reference recomputes
IN_FLIGHT = 2


def _node_norm(x):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))


@jax.jit
def _change_norms(theta, theta0):
    """[m, leaves]: per node, the norm of theta - theta_0 of each leaf."""
    return jnp.stack([_node_norm(a.astype(jnp.float32) - b.astype(jnp.float32)[None])
                      for a, b in zip(jax.tree_util.tree_leaves(theta),
                                      jax.tree_util.tree_leaves(theta0))], axis=1)


@jax.jit
def _leaf_norms(tree):
    """[m, leaves]: per node, the norm of each leaf."""
    return jnp.stack([_node_norm(x) for x in jax.tree_util.tree_leaves(tree)], axis=1)


class Run:
    """What one training run measured, for the metric readers."""

    kind = "train"


def run(cell, seed: int, seconds: float, trace_dir, devices, t0: float) -> dict:
    conf, traffic = cell.config, cell.traffic
    model, train = conf["model"], conf["train"]
    m = train["nodes"]
    cfg = model_config(cell.workload["config"], model)
    if train["gossip_backend"] != "rolled" or len(devices) != 1:
        raise ValueError("the train kind runs the rolled gossip backend on one chip")
    trainer = st.make_trainer(
        cfg, m, topology=train["topology"], compressor=train["compressor"],
        fused_gossip=train["fused_gossip"], gossip_backend=train["gossip_backend"],
        eta_theta=train["eta_theta"], eta_lambda=train["eta_lambda"],
        alpha=train["alpha"], robust=train["robust"],
        microbatches=traffic["microbatches"],
    )
    key = weights.seed_key(seed)
    wkey, rkey, nkey = (jax.random.fold_in(key, i) for i in range(3))

    feed = [jax.device_put({"tokens": b}, devices[0]) for b in
            loadgen.train_batches(traffic, m, model["vocab_size"], FEED, seed)]

    def init(k, r):
        return trainer.init(weights.make(model, k), r)

    state = jax.jit(init)(wkey, rkey)
    step = DecentralizedTrainer.step.lower(trainer, state, feed[0]).compile()
    gamma = trainer.consensus.gamma
    if abs(gamma - train["gamma"]) > 1e-12 * train["gamma"]:
        raise RuntimeError(f"program's CHOCO gamma {gamma} is not the configured {train['gamma']}")

    # the first rounds: the window's own call and feed, read for the comparison
    make = jax.jit(lambda k: weights.make(model, k))
    losses, prog = [], {}
    for i in range(PROBE):
        state, aux = step(state, feed[i])
        losses.append(np.asarray(aux["losses"], np.float64))
        if i in (0, PROBE - 1):
            tag = "1" if i == 0 else "_last"
            theta0 = make(wkey)
            prog[f"change{tag}"] = np.asarray(_change_norms(state.theta, theta0), np.float64)
            del theta0
            prog[f"hat{tag}"] = np.asarray(_leaf_norms(state.consensus.theta_hat), np.float64)
            prog[f"s{tag}"] = np.asarray(_leaf_norms(state.consensus.s), np.float64)
    prog["losses"] = np.stack(losses)
    tokens_per_step = m * traffic["batch_per_node"] * traffic["seq_len"]

    # the window
    if trace_dir is not None:
        seconds = min(seconds, TRACE_S)
        tr.start(trace_dir)
    setup_s = time.perf_counter() - t0
    inflight, steps, failed = collections.deque(), 0, 0
    with TraceAnnotation(tr.WINDOW):
        w0 = time.perf_counter()
        while True:
            with TraceAnnotation("bench.batch"):
                batch = feed[(PROBE + steps) % FEED]
            with TraceAnnotation("bench.step"):
                state, aux = step(state, batch)
            inflight.append(aux["losses"])
            steps += 1
            if len(inflight) > IN_FLIGHT:
                with TraceAnnotation("bench.sync"):
                    last = np.asarray(inflight.popleft())
                    failed += int(not np.isfinite(last).all())
            if time.perf_counter() - w0 >= seconds:
                break
        with TraceAnnotation("bench.sync"):
            while inflight:
                last = np.asarray(inflight.popleft())
                failed += int(not np.isfinite(last).all())
        w1 = time.perf_counter()
    if trace_dir is not None:
        tr.stop()

    r = Run()
    r.model, r.traffic, r.train, r.devices = model, traffic, train, devices
    r.steps, r.window_s, r.last_losses = steps, w1 - w0, last.tolist()
    r.tokens = steps * tokens_per_step
    r.hlo_text = step.as_text() if trace_dir is not None else None
    r.memory_peak_bytes = memory_peak_bytes(devices)
    del state, aux, step, feed, inflight
    gc.collect()

    batches = loadgen.train_batches(traffic, m, model["vocab_size"], PROBE, seed)
    ref = adgda_ref.run(model, train, wkey, batches, nkey, devices, steps=PROBE)
    numbers = compare.train_numbers(prog, ref)
    return {
        "setup_s": setup_s,
        "attempted": steps,
        "failed": failed,
        "e2e": {"train_tokens_per_s": r.tokens / r.window_s, "setup_s": setup_s},
        "numbers": numbers,
        "run": r,
        "prog": prog,
        "reference": ref,
    }
