"""The composable DecentralizedTrainer API: shims, new compositions,
local_steps x momentum, bits accounting."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ADGDAConfig,
    ChocoConsensus,
    DecentralizedTrainer,
    DRDSGDConfig,
    DRFAConfig,
    ExactConsensus,
    LocalUpdate,
    ProjectedAscent,
    TrainerState,
    adgda_trainer,
    drfa_trainer,
)
from repro.core import dro
from repro.core.topology import make_topology
from repro.optim import make_schedule, sgd

M = 6


def _quadratic_loss():
    def loss_fn(params, batch, rng):
        return 0.5 * jnp.sum((params["w"] - batch["mu"]) ** 2)

    batch = {"mu": jnp.asarray([[-3.0], [0.0], [0.0], [0.0], [0.0], [3.0]])}
    return loss_fn, batch


# ------------------------------------------------------------------- shims
def test_deprecated_shims_importable_with_old_signatures():
    from repro.core import ADGDA, DRDSGD, DRFA
    from repro.core.adgda import ADGDAState  # noqa: F401 (alias import works)

    loss_fn, batch = _quadratic_loss()
    with pytest.warns(DeprecationWarning):
        tr = ADGDA(ADGDAConfig(num_nodes=M, compressor="q4b"), loss_fn)
    state = tr.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    state, aux = tr.step(state, batch)
    assert np.isfinite(float(aux["mean_loss"]))
    assert tr.bits_per_round(state) > 0
    assert isinstance(tr, DecentralizedTrainer)

    with pytest.warns(DeprecationWarning):
        tr = DRDSGD(DRDSGDConfig(num_nodes=M, alpha=1.0), loss_fn)
    state = tr.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    state, aux = tr.step(state, batch)
    assert np.isfinite(float(aux["worst_loss"]))

    with pytest.warns(DeprecationWarning):
        tr = DRFA(DRFAConfig(num_nodes=M, local_steps=2), loss_fn)
    kb = {"mu": jnp.broadcast_to(batch["mu"][:, None], (M, 2, 1))}
    state = tr.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    state, aux = tr.step(state, kb)
    assert np.isfinite(float(aux["worst_loss"]))


# ------------------------------------------------ local_steps x momentum
def test_local_steps_composes_with_momentum():
    """The seed trainer asserted local_steps and momentum mutually exclusive;
    with the optimizer carried in trainer state they compose."""
    loss_fn, _ = _quadratic_loss()
    K = 4
    # asymmetric: w=0 starts at worst 18; robust optimum balances to ~4.5
    offsets = jnp.asarray([[0.0]] * 5 + [[6.0]])
    cfg = ADGDAConfig(num_nodes=M, topology="ring", compressor="q8b", alpha=0.05,
                      eta_theta=0.03, eta_lambda=0.1, lr_decay=0.97,
                      local_steps=K, momentum=0.9)
    tr = adgda_trainer(cfg, loss_fn)
    kb = {"mu": jnp.repeat(offsets, K, axis=1)}
    state = tr.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    for _ in range(200):
        state, aux = tr.step(state, kb)
    # momentum buffer exists, is stacked, and was actually used
    assert state.opt.mu["w"].shape == (M, 1)
    assert float(jnp.abs(state.opt.mu["w"]).max()) > 0
    # moved substantially toward the robust solution despite K-step drift
    assert float(aux["worst_loss"]) < 9.0
    assert float(aux["consensus_err"]) < 0.5


def test_local_steps_one_equals_single_step_path():
    """K=1 must reduce to the single-step oracle bit-for-bit (same ops)."""
    loss_fn, batch = _quadratic_loss()
    base = ADGDAConfig(num_nodes=M, topology="ring", compressor="q8b", alpha=0.05,
                       eta_theta=0.05, eta_lambda=0.05, momentum=0.9)
    t1 = adgda_trainer(base, loss_fn)
    tk = adgda_trainer(dataclasses.replace(base, local_steps=1), loss_fn)
    s1 = t1.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    sk = tk.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    with jax.disable_jit():
        for _ in range(3):
            s1, _ = t1.step_impl(s1, batch)
            sk, _ = tk.step_impl(sk, batch)
    np.testing.assert_array_equal(np.asarray(s1.theta["w"]), np.asarray(sk.theta["w"]))


def test_local_steps_with_adam():
    """K local steps compose with any optimizer, not just SGD."""
    loss_fn, _ = _quadratic_loss()
    K = 3
    cfg = ADGDAConfig(num_nodes=M, topology="ring", compressor="q8b", alpha=0.05,
                      eta_theta=0.05, eta_lambda=0.05, local_steps=K, optimizer="adam")
    tr = adgda_trainer(cfg, loss_fn)
    kb = {"mu": jnp.repeat(jnp.asarray([[-3.0], [0.0], [0.0], [0.0], [0.0], [3.0]]), K, axis=1)}
    state = tr.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    for _ in range(30):
        state, aux = tr.step(state, kb)
    assert np.isfinite(float(aux["mean_loss"]))
    assert state.opt.nu["w"].shape == (M, 1)  # second moment carried


def test_local_steps_and_microbatches_mutually_exclusive():
    with pytest.raises(ValueError, match="do not compose"):
        LocalUpdate(optimizer=sgd(0.1), schedule=make_schedule("const", 0.1),
                    local_steps=2, microbatches=2)


# ----------------------------------------------------- new compositions
def test_adam_adgda_one_liner():
    loss_fn, batch = _quadratic_loss()
    cfg = ADGDAConfig(num_nodes=M, compressor="q4b", optimizer="adam",
                      schedule="cosine", warmup=5, total_steps=200,
                      eta_theta=0.3, alpha=0.05, eta_lambda=0.1)
    tr = adgda_trainer(cfg, loss_fn)
    state = tr.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    etas = []
    for _ in range(40):
        etas.append(float(tr.local.lr(state.opt)))  # the rate this round applies
        state, aux = tr.step(state, batch)
    assert etas[0] == pytest.approx(0.0)  # warmup starts at zero
    assert max(etas) <= 0.3 + 1e-6
    assert np.isfinite(float(aux["worst_loss"]))


def test_custom_composition_robust_exact_gossip():
    """Novel combination in a few lines: chi2 projected-ascent dual over
    *uncompressed* gossip — no new trainer class required."""
    loss_fn, batch = _quadratic_loss()
    topo = make_topology("ring", M)
    prior = jnp.full((M,), 1.0 / M)
    sched = make_schedule("exp", 0.05, decay=0.995)
    tr = DecentralizedTrainer(
        loss_fn,
        num_nodes=M,
        local=LocalUpdate(optimizer=sgd(sched, momentum=0.5), schedule=sched),
        dual=ProjectedAscent(prior=prior, alpha=0.05, eta_lambda=0.05,
                             regularizer=dro.make_regularizer("chi2"), topology=topo),
        consensus=ExactConsensus(topo),
        prior=prior,
    )
    state = tr.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    for _ in range(300):
        state, aux = tr.step(state, batch)
    lam = np.asarray(aux["lambda_mean"])
    assert lam[0] + lam[-1] > 0.5  # dual concentrates on the extremes
    assert float(aux["consensus_err"]) < 0.1


# ------------------------------------------------------- bits accounting
def test_drfa_honors_momentum():
    """The seed DRFA declared config.momentum but silently ignored it; the
    composed trainer honors it (documented behavior change, default 0.0
    unchanged)."""

    def loss_fn(params, b, rng):
        return 0.5 * jnp.sum((params["w"] - b) ** 2)

    kb = jnp.broadcast_to(jnp.arange(M, dtype=jnp.float32)[:, None, None], (M, 2, 1))
    tr = drfa_trainer(DRFAConfig(num_nodes=M, local_steps=2, momentum=0.9), loss_fn)
    state = tr.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    state, _ = tr.step(state, kb)
    assert state.opt.mu["w"].shape == (M, 1)
    assert float(jnp.abs(state.opt.mu["w"]).max()) > 0


def test_drfa_bits_per_iteration():
    def loss_fn(params, b, rng):
        return 0.5 * jnp.sum((params["w"] - b) ** 2)

    K = 10
    tr = drfa_trainer(DRFAConfig(num_nodes=M, local_steps=K, participation=0.5), loss_fn)
    state = tr.init({"w": jnp.zeros((100,))}, jax.random.PRNGKey(0))
    per_round = tr.bits_per_round(state)
    per_iter = tr.bits_per_round(state, per_iteration=True)
    assert per_round == pytest.approx(2.0 * 3 * 100 * 32.0)  # |U|=3 up+down f32
    assert per_iter == pytest.approx(per_round / K)


def test_adgda_bits_include_dual_gossip():
    loss_fn, _ = _quadratic_loss()
    cfg = ADGDAConfig(num_nodes=M, topology="ring", compressor="none")
    robust = adgda_trainer(cfg, loss_fn)
    frozen = adgda_trainer(dataclasses.replace(cfg, robust=False), loss_fn)
    params = {"w": jnp.zeros((50,))}
    sr = robust.init(params, jax.random.PRNGKey(0))
    sf = frozen.init(params, jax.random.PRNGKey(0))
    # robust pays the uncompressed lambda gossip (m floats/neighbor) on top
    assert robust.bits_per_round(sr) == frozen.bits_per_round(sf) + 32.0 * M * 2
    # per-iteration equals per-round when local_steps == 1
    assert robust.bits_per_round(sr, per_iteration=True) == robust.bits_per_round(sr)


def test_state_is_a_plain_namedtuple_pytree():
    """TrainerState round-trips through tree flatten/unflatten (checkpointing
    and sharding-spec construction rely on this)."""
    loss_fn, batch = _quadratic_loss()
    tr = adgda_trainer(ADGDAConfig(num_nodes=M, compressor="q4b", momentum=0.9), loss_fn)
    state = tr.init({"w": jnp.zeros((1,))}, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(state)
    state2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(state2, TrainerState)
    state3, _ = tr.step(state2, batch)
    assert int(state3.step) == 1
