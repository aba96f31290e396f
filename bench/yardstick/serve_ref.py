"""The serving comparison: served tokens against the plain reference.

For a seeded sample of finished requests (the longest among them), the
reference runs once over each prompt followed by its served tokens, in
float32, and reads at each position where a token was served how far that
token's logit lies below the reference's best there. Greedy decoding serves
the best token, so a sound server's gaps are round-off: only near-ties can
flip. The widest gap over the sample is compared.

The control puts the reference in the program's place at one precision step
below the configuration's (float8 e4m3): at the same positions it reads the
gap of the token the float8 logits put first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import reference, weights

PAD = 256  # sequences are padded to a multiple of this: few programs to compile


def sample(answers, seed: int, tokens: int):
    """The longest answer, then others drawn from the seed, until the sample
    holds at least ``tokens`` served tokens. ``answers``: (prompt, output)."""
    if not answers:
        return []
    order = np.random.default_rng(np.random.SeedSequence((int(seed), 5))).permutation(len(answers))
    longest = max(range(len(answers)), key=lambda i: len(answers[i][1]))
    picked, n = [longest], len(answers[longest][1])
    for i in order:
        if n >= tokens:
            break
        if i != longest:
            picked.append(int(i))
            n += len(answers[i][1])
    return [answers[i] for i in picked]


@functools.partial(jax.jit, static_argnames=("model_items", "prec"))
def _gaps(params, seq, first, served, model_items, prec):
    """Reference gaps at positions first .. first+len(served)-1 of seq."""
    model = dict(model_items)
    lg = reference.logits(params, seq, model, "f32")
    pos = first + jnp.arange(served.shape[0])
    rows = lg[pos]
    best = rows.max(-1)
    if prec == "f32":
        tok = served
    else:
        tok = jnp.argmax(reference.logits(params, seq, model, prec)[pos], -1)
    return best - jnp.take_along_axis(rows, tok[:, None], -1)[:, 0]


def _widest(model: dict, wkey, answers, device, prec: str) -> float:
    if not answers:
        return float("inf")
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, str, bool))))
    params = jax.jit(lambda k: weights.make(model, k))(jax.device_put(wkey, device))
    widest = 0.0
    with jax.default_matmul_precision("highest"):
        for prompt, out in answers:
            seq = list(prompt) + list(out[:-1])
            padded = np.zeros(-(-len(seq) // PAD) * PAD, np.int32)
            padded[: len(seq)] = seq
            served = np.zeros(padded.size, np.int32)
            served[: len(out)] = out
            g = _gaps(params, jnp.asarray(padded), jnp.int32(len(prompt) - 1),
                      jnp.asarray(served), items, prec)
            widest = max(widest, float(np.max(np.asarray(g)[: len(out)])))
    return widest


def token_gap(model: dict, wkey, answers, device) -> float:
    """Widest gap of a served token below the reference's best."""
    return _widest(model, wkey, answers, device, "f32")


def control_gap(model: dict, wkey, answers, device) -> float:
    """The same, for the tokens the float8 control puts first."""
    return _widest(model, wkey, answers, device, "fp8")
