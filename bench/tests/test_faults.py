"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a run of
a tiny copy of a cell on the CPU (``tests/data``), with the real cell's limits
and one fault planted in the program: a round that returns its state
unchanged; half of each node's batch left out, the mean taken over the rest;
every CHOCO payload encoded as zero; the exchange between nodes left out (each
node mixes its own payload only); a served token altered where it is produced.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

import run as harness
from repro.core import trainer as T
from repro.kernels import choco_fused as CF
from repro.serving import engine as E
from yardstick import registry

DATA = registry.BENCH / "tests" / "data"


def tiny(name: str, limits_of: str):
    real = registry.load_cell(limits_of)
    registry_data, registry.DATA = registry.DATA, DATA
    try:
        cell = registry.load_cell(name)
    finally:
        registry.DATA = registry_data
    return dataclasses.replace(cell, workload={**cell.workload, "limits": real.limits})


def drive(cell, seed=20261017, seconds=1.0):
    return harness.run_cell(cell, seed, seconds, False, jax.devices()[:1], time.perf_counter())


@pytest.fixture(scope="module")
def train_cell():
    return tiny("tiny-train", "adgda-q17b-ring2-s512")


@pytest.fixture(scope="module")
def serve_cell():
    return tiny("tiny-serve", "serve-q4b-chat")


def test_sound_training_run_is_correct(train_cell):
    assert drive(train_cell)["correct"]


def test_state_left_unchanged(train_cell, monkeypatch):
    real = T.DecentralizedTrainer.step_impl

    def frozen(self, state, batch):
        _, aux = real(self, state, batch)
        return state, aux

    monkeypatch.setattr(T.DecentralizedTrainer, "step_impl", frozen)
    res = drive(train_cell)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] > res["checks"]["change_gap"]["limit"]


def test_half_the_batch_left_out(train_cell, monkeypatch):
    real = T.LocalUpdate.step

    def half(self, loss_fn, theta, opt_state, batch, node_keys, weights_fn):
        tok = batch["tokens"]
        b = tok.shape[1]
        if b > 1:  # the first half of the rows twice: the mean over half the batch
            tok = jnp.concatenate([tok[:, : b // 2]] * 2, axis=1)
        else:  # one row: the mean over its first half
            tok = tok[:, :, : tok.shape[2] // 2]
        return real(self, loss_fn, theta, opt_state, {**batch, "tokens": tok}, node_keys,
                    weights_fn)

    monkeypatch.setattr(T.LocalUpdate, "step", half)
    assert not drive(train_cell)["correct"]


def test_payloads_encoded_as_zero(train_cell, monkeypatch):
    real = CF.fused_round_leaf

    def zero(leaf, hat, s, *a, **kw):
        theta, _, _ = real(leaf, hat, s, *a, **kw)
        return theta, hat, s  # q = 0: hat and s never move

    monkeypatch.setattr(CF, "fused_round_leaf", zero)
    res = drive(train_cell)
    assert not res["correct"]
    assert res["checks"]["hat_gap"]["value"] > res["checks"]["hat_gap"]["limit"]


def test_exchange_left_out(train_cell, monkeypatch):
    real = CF.fused_round_leaf

    def alone(leaf, hat, s, key, shifts, *a, **kw):
        return real(leaf, hat, s, key, [(sh, w) for sh, w in shifts if sh == 0], *a, **kw)

    monkeypatch.setattr(CF, "fused_round_leaf", alone)
    res = drive(train_cell)
    assert not res["correct"]
    assert res["checks"]["s_gap"]["value"] > res["checks"]["s_gap"]["limit"]


def test_sound_serving_run_is_correct(serve_cell):
    assert drive(serve_cell, seconds=2.0)["correct"]


def test_served_token_altered(serve_cell, monkeypatch):
    real_init = E.ServeEngine.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        greedy = self._sample

        def altered(logits, key):
            tok = greedy(logits, key)
            return tok.at[0].set((tok[0] + 1) % logits.shape[-1])

        self._sample = altered

    monkeypatch.setattr(E.ServeEngine, "__init__", init)
    res = drive(serve_cell, seconds=2.0)
    assert not res["correct"]
