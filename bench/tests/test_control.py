"""The control comes out not correct: the plain reference put in the
program's place one precision step below the configuration's (float8 e4m3
for bfloat16), read against the float32 reference at a tiny size with each
real cell's limits. (On the chip, at the cells' own sizes, ``calibrate.py
--control`` gives the readings its limits were set from.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from yardstick import adgda_ref, compare, loadgen, reference, registry, serve_ref, weights

DATA = registry.BENCH / "tests" / "data"


def _conf(name):
    return registry.load_json(DATA / "configs" / f"{name}.json")


@pytest.mark.parametrize("seed", [1, 2**33 + 5, 77])
def test_training_control_fails(seed):
    conf, traffic = _conf("tiny-train"), registry.load_json(DATA / "traffic" / "tiny-train.json")
    limits = registry.load_cell("adgda-q17b-ring2-s512").limits
    model, train = conf["model"], conf["train"]
    key = weights.seed_key(seed)
    wkey, nkey = jax.random.fold_in(key, 0), jax.random.fold_in(key, 2)
    batches = loadgen.train_batches(traffic, train["nodes"], model["vocab_size"], 3, seed)
    dev = jax.devices()[:1]
    ref = adgda_ref.run(model, train, wkey, batches, nkey, dev)
    ctl = adgda_ref.run(model, train, wkey, batches, jax.random.fold_in(nkey, 7), dev, prec="fp8")
    ok, _ = compare.judge(compare.train_numbers(ctl, ref), limits)
    assert not ok


@pytest.mark.parametrize("seed", [1, 2**33 + 5, 77])
def test_serving_control_fails(seed):
    model = _conf("tiny-serve")["model"]
    limit = registry.load_cell("serve-q4b-chat").limits["token_gap"]
    wkey = jax.random.fold_in(weights.seed_key(seed), 0)
    dev = jax.devices()[0]
    params = jax.jit(lambda k: weights.make(model, k))(wkey)
    # greedy answers of the float32 reference itself, 4 prompts x 48 tokens
    logits = jax.jit(lambda s: reference.logits(params, s, model))
    rng = np.random.default_rng(seed)
    answers = []
    for _ in range(4):
        seq = np.zeros(128, np.int32)
        seq[:32] = rng.integers(0, model["vocab_size"], 32)
        for n in range(32, 80):
            seq[n] = int(np.argmax(logits(jnp.asarray(seq))[n - 1]))
        answers.append((seq[:32].tolist(), seq[32:80].tolist()))
    assert serve_ref.token_gap(model, wkey, answers, dev) <= limit
    assert serve_ref.control_gap(model, wkey, answers, dev) > limit
