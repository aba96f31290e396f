"""End-to-end behaviour of the full system: the paper's central claims on
the synthetic heterogeneous pipeline, driver round-trips, checkpointing.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ADGDAConfig, adgda_trainer, choco_sgd
from repro.data import (
    class_shard_classification,
    instrument_shift_classification,
    rotated_minority_classification,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------ shared setup
def _logistic_init(dim, classes):
    return {"w": jnp.zeros((dim, classes)), "b": jnp.zeros((classes,))}


def _logistic_loss(params, batch, rng):
    x, y = batch
    logits = x @ params["w"] + params["b"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return (logz - gold).mean()


def _accuracy(params, x, y):
    pred = np.asarray(jnp.argmax(x @ params["w"] + params["b"], axis=-1))
    return float((pred == np.asarray(y)).mean())


def _train(trainer, data, steps=150, batch=64, seed=0):
    params = _logistic_init(data.dim, data.num_classes)
    state = trainer.init(params, jax.random.PRNGKey(seed))
    gen = data.batches(batch, seed=seed)
    for _ in range(steps):
        xb, yb = next(gen)
        state, _ = trainer.step(state, (jnp.asarray(xb), jnp.asarray(yb)))
    return trainer.network_mean(state), state


def _worst_val_acc(params, data):
    return min(_accuracy(params, jnp.asarray(x), y) for x, y in zip(data.val_x, data.val_y))


# ------------------------------------------------------------- paper claims
def test_adgda_beats_choco_sgd_worst_node():
    """Paper Table 2's qualitative claim: distributionally robust training
    massively improves the worst-distribution accuracy at the same budget.
    Uses the rotated-minority construction (no linear model fits both
    sub-populations, so average-risk training sacrifices the minority)."""
    m = 10
    data = rotated_minority_classification(num_nodes=m, seed=1)
    common = dict(num_nodes=m, topology="ring", compressor="q4b", eta_theta=0.3, lr_decay=0.99)
    robust = adgda_trainer(ADGDAConfig(alpha=0.05, eta_lambda=0.2, **common), _logistic_loss)
    standard = choco_sgd(ADGDAConfig(**common), _logistic_loss)
    p_r, _ = _train(robust, data, steps=600, batch=50)
    p_s, _ = _train(standard, data, steps=600, batch=50)
    w_r, w_s = _worst_val_acc(p_r, data), _worst_val_acc(p_s, data)
    assert w_r > w_s + 0.05, f"robust {w_r:.3f} vs standard {w_s:.3f}"


def test_adgda_closes_instrument_gap():
    """COOS7-analog: the accuracy gap between the two 'microscopes' shrinks
    under AD-GDA (paper Fig. 2 / Table 4b)."""
    data = instrument_shift_classification(num_nodes=10, minority_nodes=2, seed=1)
    common = dict(num_nodes=10, topology="torus", compressor="q8b", eta_theta=0.5)
    robust = adgda_trainer(ADGDAConfig(alpha=0.01, eta_lambda=0.05, **common), _logistic_loss)
    standard = choco_sgd(ADGDAConfig(**common), _logistic_loss)
    p_r, _ = _train(robust, data, steps=200)
    p_s, _ = _train(standard, data, steps=200)

    def gap(p):
        accs = [_accuracy(p, jnp.asarray(x), y) for x, y in zip(data.val_x, data.val_y)]
        return abs(accs[0] - accs[1])

    assert gap(p_r) < gap(p_s) + 1e-6
    assert _worst_val_acc(p_r, data) >= _worst_val_acc(p_s, data) - 0.02


def test_smaller_alpha_more_robust():
    """Paper Table 4: smaller regularization -> less constrained adversary ->
    better worst-case accuracy (alpha=inf recovers standard training)."""
    m = 10
    data = rotated_minority_classification(num_nodes=m, seed=2)
    worst = {}
    for alpha in (100.0, 0.05):
        tr = adgda_trainer(
            ADGDAConfig(num_nodes=m, topology="ring", compressor="none",
                        alpha=alpha, eta_theta=0.3, eta_lambda=0.2, lr_decay=0.99),
            _logistic_loss,
        )
        p, _ = _train(tr, data, steps=600, batch=50)
        worst[alpha] = _worst_val_acc(p, data)
    assert worst[0.05] > worst[100.0] + 0.03, worst


def test_consensus_error_decreases():
    """CHOCO consensus: with a decaying step the node models converge."""
    m = 6
    data = class_shard_classification(num_nodes=m, dim=16, seed=0)
    tr = adgda_trainer(
        ADGDAConfig(num_nodes=m, topology="ring", compressor="q8b",
                    alpha=0.1, eta_theta=0.3, eta_lambda=0.02, lr_decay=0.97),
        _logistic_loss,
    )
    params = _logistic_init(data.dim, data.num_classes)
    state = tr.init(params, KEY)
    gen = data.batches(32, seed=0)
    errs = []
    for _ in range(120):
        xb, yb = next(gen)
        state, aux = tr.step(state, (jnp.asarray(xb), jnp.asarray(yb)))
        errs.append(float(aux["consensus_err"]))
    assert np.mean(errs[-10:]) < 0.25 * max(errs) + 1e-8


def test_dual_variable_upweights_worst_node():
    """lambda must concentrate on the node with the largest loss."""
    m = 4
    rng = np.random.default_rng(0)
    x = rng.normal(size=(m, 256, 8)).astype(np.float32)
    w_true = rng.normal(size=(8,))
    y = np.stack([
        (x[i] @ w_true > 0).astype(np.int32) if i < 3
        else rng.integers(0, 2, 256).astype(np.int32)  # node 3: pure noise
        for i in range(m)
    ])
    tr = adgda_trainer(
        ADGDAConfig(num_nodes=m, topology="mesh", compressor="none",
                    alpha=0.05, eta_theta=0.3, eta_lambda=0.1),
        _logistic_loss,
    )
    state = tr.init(_logistic_init(8, 2), KEY)
    for _ in range(150):
        idx = rng.integers(0, 256, (m, 32))
        xb = np.take_along_axis(x, idx[:, :, None], 1)
        yb = np.take_along_axis(y, idx, 1)
        state, aux = tr.step(state, (jnp.asarray(xb), jnp.asarray(yb)))
    lam = np.asarray(state.lam).mean(0)
    assert lam[3] == lam.max()
    assert lam[3] > 1.5 / m


# --------------------------------------------------------------- drivers
@pytest.mark.slow
def test_train_driver_end_to_end(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-1.7b",
         "--reduced", "--steps", "3", "--nodes", "2", "--batch-per-node", "1",
         "--seq", "32", "--log-every", "1",
         "--checkpoint", str(tmp_path / "ckpt")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "worst=" in out.stdout


@pytest.mark.slow
def test_serve_driver_end_to_end():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "recurrentgemma-2b",
         "--reduced", "--batch", "2", "--prompt-len", "16", "--gen", "6"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ms/token" in out.stdout


@pytest.mark.parametrize("alone", [False, True], ids=["in-repo", "alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    """No accelerator, no result: the smoke never falls back to the CPU."""
    import shutil

    script = os.path.join(REPO, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, cwd=os.path.dirname(script), env=env, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    # alone, the import of the program fails first
    assert ("ModuleNotFoundError" if alone else "no TPU") in out.stderr


@pytest.mark.parametrize("from_env", [False, True], ids=["repo-default", "env"])
def test_compile_cache_location(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache sits at
    one fixed, git-ignored path inside the checkout."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = (
        "import jax; from repro.launch.compile_cache import configure_compile_cache as c; "
        "print(c()); print(jax.config.jax_compilation_cache_dir)"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=REPO, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    chosen, configured = out.stdout.split()[-2:]
    if from_env:
        assert chosen == configured == str(tmp_path / "cc")
    else:
        assert chosen == configured == os.path.join(REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()


def test_checkpoint_roundtrip_model(tmp_path):
    from repro.checkpoint import latest_step, restore, save
    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = get_config("qwen3-1.7b").reduced()
    params = T.init_model(KEY, cfg)
    fname = save(str(tmp_path / "model"), params, step=7)
    assert latest_step(str(tmp_path / "model")) == 7
    back = restore(fname, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params))
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_local_steps_trade_compute_for_communication():
    """Paper §6 extension: K local SGD steps between gossip rounds.  At an
    EQUAL communication budget (same number of gossip rounds) and a local
    learning rate scaled down to bound consensus drift, K=5 matches or beats
    the fully-communicating run — i.e. local computation substitutes for
    communication.  (The naive 1/K-rounds framing was measured first and
    refuted: at eta 0.3 the drift costs ~33 pts; recorded in EXPERIMENTS.)"""
    m = 8
    data = rotated_minority_classification(num_nodes=m, seed=0)

    def run(local_steps, eta, rounds=600):
        cfg = ADGDAConfig(num_nodes=m, topology="ring", compressor="q4b",
                          alpha=0.05, eta_theta=eta, eta_lambda=0.2,
                          lr_decay=0.99, local_steps=local_steps)
        tr = adgda_trainer(cfg, _logistic_loss)
        state = tr.init(_logistic_init(data.dim, data.num_classes), jax.random.PRNGKey(0))
        gen = data.batches(50 * local_steps, seed=0)
        for _ in range(rounds):
            xb, yb = next(gen)
            state, _ = tr.step(state, (jnp.asarray(xb), jnp.asarray(yb)))
        return _worst_val_acc(tr.network_mean(state), data), tr.bits_per_round(state) * rounds

    w1, bits1 = run(1, eta=0.3)
    w5, bits5 = run(5, eta=0.1)
    assert bits5 == pytest.approx(bits1, rel=1e-6)  # same wire budget
    assert w5 > w1 - 0.03, (w5, w1)
