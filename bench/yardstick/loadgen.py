"""Inputs made from ``--seed``: the open-loop request schedule of a serving
cell and the token batches of a training cell.

Serving. Arrivals are scheduled in wall seconds, not in engine ticks, so a
slower server is offered the same load and its queue grows. Every seed gets
the same work in another order: the prompt lengths, output lengths and
inter-arrival gaps are the quantiles of their distributions at n evenly
spaced points (n = rate x seconds), and the run's seed shuffles the gaps, the
prompt lengths and the output lengths, each apart, and draws the prompt
tokens. So every seed offers the same requests at the same mean rate, with
arrival times and pairings of its own. Lengths follow
bounded Zipf laws, prompt tokens a Zipf unigram law under a permutation of the
vocabulary drawn from the seed (the draws of the program's
``serving/loadgen.py``, with arrivals in seconds).

Training. Per-node token batches [m, b, S] with node-skewed Zipf unigram
statistics: one Zipf law, a vocabulary permutation per node (the draw of the
program's ``data.node_token_stream``).
"""
from __future__ import annotations

import dataclasses

import numpy as np


def bounded_zipf_probs(a: float, lo: int, hi: int) -> np.ndarray:
    """P(k) proportional to (k - lo + 1)^-a for k in [lo, hi]."""
    if not hi >= lo >= 0:
        raise ValueError((lo, hi))
    p = np.arange(1, hi - lo + 2, dtype=np.float64) ** (-a)
    return p / p.sum()


def _quantiles(probs: np.ndarray, lo: int, n: int) -> np.ndarray:
    """The law's values at the n evenly spaced quantiles (i + 0.5) / n."""
    cdf = np.cumsum(probs)
    u = (np.arange(n) + 0.5) / n
    return lo + np.minimum(np.searchsorted(cdf, u, side="right"), probs.size - 1)


def _rng(seed: int, lane: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence((int(seed), lane)))


def _zipf_tokens(rng, perm: np.ndarray, size, a: float) -> np.ndarray:
    probs = bounded_zipf_probs(a, 0, perm.size - 1)
    return perm[rng.choice(perm.size, size=size, p=probs)].astype(np.int32)


@dataclasses.dataclass(frozen=True)
class Arrival:
    t: float  # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int


def schedule(traffic: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> list[Arrival]:
    """The open-loop schedule of one run: n = round(rate x seconds) requests
    whose arrivals span [0, seconds)."""
    n = max(1, int(round(rate * seconds)))
    plen = _quantiles(bounded_zipf_probs(traffic["prompt_zipf"], traffic["prompt_min"],
                                         traffic["prompt_max"]), traffic["prompt_min"], n)
    olen = _quantiles(bounded_zipf_probs(traffic["output_zipf"], traffic["output_min"],
                                         traffic["output_max"]), traffic["output_min"], n)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate  # Exp(rate) quantiles
    gaps = _rng(seed, 4).permutation(gaps)
    order = _rng(seed, 1)
    plen, olen = order.permutation(plen), order.permutation(olen)
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / gaps.sum())
    tok = _rng(seed, 2)
    perm = tok.permutation(vocab)
    return [Arrival(float(t[i]), _zipf_tokens(tok, perm, int(plen[i]), traffic["token_zipf"]),
                    int(olen[i])) for i in range(n)]


def train_batches(traffic: dict, nodes: int, vocab: int, count: int, seed: int) -> list[np.ndarray]:
    """``count`` distinct batches [nodes, batch_per_node, seq_len] of int32."""
    rng = _rng(seed, 3)
    perms = np.stack([rng.permutation(vocab) for _ in range(nodes)])
    probs = bounded_zipf_probs(traffic["token_zipf"], 0, vocab - 1)
    shape = (nodes, traffic["batch_per_node"], traffic["seq_len"])
    node = np.arange(nodes)[:, None, None]
    return [perms[node, rng.choice(vocab, size=shape, p=probs)].astype(np.int32)
            for _ in range(count)]
