"""The comparison that decides ``correct``: numbers of the program's run
against the plain reference, each against its limit.

Training (per node, the first three rounds):

* ``loss_gap``   -- widest relative gap |f_program - f_ref| / |f_ref| over the
  three rounds' losses;
* ``grad_gap``   -- the first update as the optimizer applied it,
  theta_1 - theta_0, by worst leaf: |‖d_prog‖ - ‖d_ref‖| over the larger of
  ‖d_ref‖ and the median leaf's ‖d_ref‖;
* ``change_gap`` -- the same for theta_3 - theta_0 after the three rounds;
* ``hat_gap``    -- the same for the leaf norms of the CHOCO estimate hat
  (the sum of a node's encoded payloads), after the first and the third round;
* ``s_gap``      -- the same for s (the mixed sum of the neighbours' payloads).

A leaf whose reference gradient is under a thousandth of the median leaf's
moves by round-off alone and is left out of the change gaps; hat and s hold
quantized weights, not changes, and every leaf counts.

Serving: ``token_gap`` -- over a sample of finished requests, the widest gap by
which a served token's reference logit lies below the reference's best at that
position.
"""
from __future__ import annotations

import numpy as np

QUIET_LEAF = 1e-3  # reference gradient below this share of the median leaf's


def loss_gap(prog, ref) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if not np.isfinite(prog).all():
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def moving_leaves(grad_norms) -> np.ndarray:
    g = np.asarray(grad_norms, np.float64)
    return g >= QUIET_LEAF * np.median(g, axis=1, keepdims=True)


def leaf_gaps(prog, ref, keep) -> np.ndarray:
    """[m, leaves]: |prog - ref| / max(ref, median leaf's ref); 0 where
    ``keep`` is false, inf where the program's norm is not finite."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    den = np.maximum(ref, np.median(ref, axis=1, keepdims=True))
    with np.errstate(invalid="ignore"):
        g = np.where(keep, np.abs(prog - ref) / den, 0.0)
    return np.where(np.isfinite(prog), g, np.inf)


def _leaf_readings(prog: dict, ref: dict) -> dict:
    """Each leaf number -> {reading: [m, leaves] gaps} it takes the worst of."""
    keep = moving_leaves(ref["grad_norms"])
    every = np.ones_like(keep)
    reads = {
        "grad_gap": [("change1", keep)],
        "change_gap": [("change_last", keep)],
        "hat_gap": [("hat1", every), ("hat_last", every)],
        "s_gap": [("s1", every), ("s_last", every)],
    }
    return {name: {k: leaf_gaps(prog[k], ref[k], m) for k, m in r} for name, r in reads.items()}


def train_numbers(prog: dict, ref: dict) -> dict:
    out = {"loss_gap": loss_gap(prog["losses"], ref["losses"])}
    for name, reads in _leaf_readings(prog, ref).items():
        out[name] = float(max(g.max() for g in reads.values()))
    return out


def worst_leaves(prog: dict, ref: dict) -> dict:
    """Each leaf number -> (reading, node, leaf index) where it is widest."""
    out = {}
    for name, reads in _leaf_readings(prog, ref).items():
        k, g = max(reads.items(), key=lambda kv: kv[1].max())
        node, leaf = np.unravel_index(int(np.argmax(g)), g.shape)
        out[name] = (k, int(node), int(leaf))
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number at or under its limit -> correct. A number without a limit
    is reported and not judged."""
    checks = {k: {"value": v, "limit": limits.get(k)} for k, v in numbers.items()}
    ok = all(c["limit"] is None or c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
