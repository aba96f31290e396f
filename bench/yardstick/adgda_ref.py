"""Plain reference of the first AD-GDA rounds (paper Algorithm 1, arXiv
2205.15614) with CHOCO-GOSSIP compressed consensus (Koloskova et al. 2019).

Per round t and node i, from the same seeded weights and the same batches as
the program:

  losses f_i, gradient g_i           -- reference model in float32, row by row
  theta_half_i = theta_i - eta_theta * (lam_i[i] / prior_i) * g_i
  lam_i       <- sum_j w_ij P_simplex(lam_j + eta_lam (f_j e_j + alpha grad r(lam_j)))
  theta_i     <- theta_half_i + gamma (s_i - hat_i)
  q_i          = Q(theta_i - hat_i)   (stochastic b-bit quantization, paper eq. 2)
  hat_i       <- hat_i + q_i,   s_i <- s_i + sum_j w_ij q_j

with r the chi-square regularizer. Q takes a norm of its own for each block of
a leaf, as the configuration states (``quant_block_elems``, see ``blocks``). Arithmetic is float32; theta, hat and s are
stored in the dtype the configuration states, as the algorithm keeps them.
The quantizer draws its own noise (the reference imports nothing of the
program), so its hat and s differ from the program's by quantization noise
alone; the comparison reads norms, which that noise all but leaves unmoved.

Each node's arrays live on its own device (``devices[i % len(devices)]``);
hat and s wait on the host while the gradients run; the mixing moves
neighbours' payloads to each node's device, as the exchange does. ``fault``
plants one of the faults that ``correct`` has to catch, for the readings that
set its limits: ``"half_batch"`` (each node's loss and gradient over half its
rows, or half the tokens of a single row), ``"no_exchange"`` (no neighbour's
payload reaches s), ``"zero_payload"`` (every encoded payload q is zero).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import reference, weights

F32 = jnp.float32


def ring_mixing(m: int) -> np.ndarray:
    """Doubly stochastic ring weights: 1/2 each for two nodes, else 1/3 to self
    and each neighbour."""
    if m == 1:
        return np.ones((1, 1))
    if m == 2:
        return np.full((2, 2), 0.5)
    w = np.zeros((m, m))
    for i in range(m):
        for j in (i - 1, i, i + 1):
            w[i, j % m] += 1.0 / 3.0
    return w


def project_simplex(v: np.ndarray) -> np.ndarray:
    """Euclidean projection of each row onto the probability simplex."""
    out = np.empty_like(v)
    for r, row in enumerate(v):
        u = np.sort(row)[::-1]
        css = np.cumsum(u) - 1.0
        k = np.arange(1, row.size + 1)
        rho = k[u - css / k > 0][-1]
        out[r] = np.maximum(row - css[rho - 1] / rho, 0.0)
    return out


def tau(d: int, bits: int) -> float:
    lvl = float(2 ** bits)
    return 1.0 + min(d / lvl ** 2, math.sqrt(d) / lvl)


def blocks(shape: tuple, limit: int):
    """How the quantizer splits one node's leaf of ``shape`` into blocks of
    about ``limit`` elements, each with a norm and a tau of its own:
    ``(axis, count)``, or None for the whole leaf. A leaf of at most
    ``limit`` elements is one block. A layer-stacked leaf [L, ...] (1 < L <=
    128) is cut along its layer axis into equal groups of whole layers, the
    largest group that holds at most ``limit`` elements; any other leaf along
    its last axis into the fewest equal groups (at least 2, at most 512) that
    divide it. No such cut leaves the leaf whole."""
    d = math.prod(shape)
    if len(shape) == 0 or d <= limit:
        return None
    nb = shape[0] if len(shape) > 1 else 1
    if 1 < nb <= 128:
        want = max(1, limit // (d // nb))
        rows = max(r for r in range(1, min(want, nb) + 1) if nb % r == 0)
        return (0, nb // rows) if 1 < nb // rows <= 512 else None
    last = shape[-1]
    for c in range(min(max(2, -(-d // limit)), last), min(513, last + 1)):
        if last % c == 0:
            return (len(shape) - 1, c)
    return None


def quantize(x, key, bits: int):
    """Q(x) of one block: norm * sign(x) * floor(2^b |x| / norm + xi) /
    (2^b tau), levels clipped to 2^b - 1, xi uniform on [0, 1)."""
    x = x.astype(F32)
    norm = jnp.sqrt(jnp.sum(x * x))
    xi = jax.random.uniform(key, x.shape)
    lvl = jnp.clip(jnp.floor(jnp.abs(x) * (2 ** bits) / jnp.maximum(norm, 1e-30) + xi),
                   0, 2 ** bits - 1)
    return jnp.sign(x) * lvl * norm / (2 ** bits * tau(x.size, bits))


@functools.partial(jax.jit, static_argnames=("bits", "plan"))
def quantize_blocks(x, key, bits: int, plan):
    """Q applied block by block after ``plan`` (from ``blocks``)."""
    if plan is None:
        return quantize(x, key, bits)
    axis, count = plan
    shape = x.shape
    xb = x.reshape(shape[:axis] + (count, shape[axis] // count) + shape[axis + 1:])
    xb = jnp.moveaxis(xb, axis, 0)
    q = jax.vmap(lambda b, k: quantize(b, k, bits))(xb, jax.random.split(key, count))
    return jnp.moveaxis(q, 0, axis).reshape(shape)


@jax.jit
def _primal(theta, g, scale):
    return jax.tree.map(lambda p, gg: (p.astype(F32) - scale * gg).astype(p.dtype), theta, g)


@functools.partial(jax.jit, donate_argnums=0)
def _average(th, s, hat, gamma):
    return (th.astype(F32) + gamma * (s.astype(F32) - hat.astype(F32))).astype(th.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "plan", "zero"))
def _residual_q(th, hat, key, bits: int, plan, zero: bool):
    q = quantize_blocks(th.astype(F32) - hat.astype(F32), key, bits=bits, plan=plan)
    return jnp.zeros_like(q) if zero else q


@functools.partial(jax.jit, donate_argnums=0)
def _accumulate(dst, qs, w):
    """dst + sum_j w[j] qs[j] in float32, stored in dst's dtype."""
    acc = dst.astype(F32)
    for j, q in enumerate(qs):
        acc = acc + w[j] * q
    return acc.astype(dst.dtype)


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@jax.jit
def change_norms(tree, tree0):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))
                      for a, b in zip(jax.tree_util.tree_leaves(tree),
                                      jax.tree_util.tree_leaves(tree0))])


def _grad_fns(model: dict, prec: str):
    """(first, more): the loss and float32 gradient of one row; ``more`` adds
    the gradient into a donated accumulator, so a node's rows never hold
    more than one gradient's memory."""
    def vg(p, row):
        p32 = jax.tree.map(lambda x: x.astype(F32), p)
        return jax.value_and_grad(reference.seq_loss)(p32, row, model, prec)

    def more(p, row, acc):
        lv, g = vg(p, row)
        return lv, jax.tree.map(jnp.add, acc, g)

    return jax.jit(vg), jax.jit(more, donate_argnums=2)


def _rows_for(batch_i: np.ndarray, fault: str | None) -> list[np.ndarray]:
    rows = list(batch_i)
    if fault == "half_batch":
        if len(rows) > 1:
            return rows[: len(rows) // 2]
        return [rows[0][: rows[0].shape[0] // 2]]
    return rows


def run(model: dict, train: dict, seed_key, batches, noise_key, devices,
        prec: str = "f32", fault: str | None = None, steps: int = 3) -> dict:
    """The first ``steps`` rounds from the seeded weights on ``batches``
    (a list of [m, b, S] int arrays). Returns per-step losses [steps, m], the
    first gradient's leaf norms [m, leaves], and after the first and after the
    last round the leaf norms [m, leaves] of the change theta - theta_0 and of
    the CHOCO state hat and s."""
    m, bits = train["nodes"], train["bits"]
    lr, eta_l, alpha, gamma = (train[k] for k in ("eta_theta", "eta_lambda", "alpha", "gamma"))
    W = ring_mixing(m)
    prior = np.full(m, 1.0 / m)
    dev = [devices[i % len(devices)] for i in range(m)]

    make = jax.jit(lambda k: weights.make(model, k))
    theta = [make(jax.device_put(seed_key, dev[i])) for i in range(m)]
    treedef = jax.tree_util.tree_structure(theta[0])
    plans = [blocks(x.shape, train["quant_block_elems"])
             for x in jax.tree_util.tree_leaves(theta[0])]
    zero = fault == "zero_payload"
    hat = s = None  # zero before the first round
    lam = np.tile(prior, (m, 1))
    grad, grad_more = _grad_fns(model, prec)

    losses, gnorm, read = [], None, {}
    for t in range(steps):
        probe = t == 0 or t == steps - 1
        tag = "1" if t == 0 else "_last"
        hat_n, s_n = np.zeros((m, len(plans))), np.zeros((m, len(plans)))
        step_losses, half, norms_t = [], [], []
        for i in range(m):
            rows = _rows_for(np.asarray(batches[t][i]), fault)
            acc, li = None, 0.0
            for row in rows:
                row = jax.device_put(jnp.asarray(row), dev[i])
                lv, acc = grad(theta[i], row) if acc is None else grad_more(theta[i], row, acc)
                li += float(lv)
            step_losses.append(li / len(rows))
            if t == 0:
                norms_t.append(np.asarray(leaf_norms(acc)) / len(rows))
            w = lam[i, i] / prior[i]
            half.append(_primal(theta[i], acc, jnp.float32(lr * w / len(rows))))
            theta[i] = None
            del acc
        losses.append(step_losses)
        if t == 0:
            gnorm = np.stack(norms_t)
        f = np.asarray(step_losses)
        dual_grad = np.diag(f) + alpha * (-2.0 * (lam - prior) / prior)
        lam = W @ project_simplex(lam + eta_l * dual_grad)

        leaves = [jax.tree_util.tree_leaves(h) for h in half]
        del half
        hats = [jax.tree_util.tree_leaves(h) for h in hat] if hat else None
        ss = [jax.tree_util.tree_leaves(x) for x in s] if s else None
        new_hat = [[None] * len(leaves[0]) for _ in range(m)]
        new_s = [[None] * len(leaves[0]) for _ in range(m)]
        for l in range(len(leaves[0])):
            qs = []
            for i in range(m):
                k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(noise_key, t), l), i)
                k = jax.device_put(k, dev[i])
                if hats is None:  # hat = s = 0: theta stays, q = Q(theta)
                    h0 = jnp.zeros_like(leaves[i][l])
                    qs.append(_residual_q(leaves[i][l], h0, k, bits=bits, plan=plans[l],
                                          zero=zero))
                    new_hat[i][l] = _accumulate(h0, (qs[i],), (1.0,))
                else:
                    h = jax.device_put(hats[i][l], dev[i])
                    leaves[i][l] = _average(leaves[i][l], jax.device_put(ss[i][l], dev[i]), h,
                                            jnp.float32(gamma))
                    qs.append(_residual_q(leaves[i][l], h, k, bits=bits, plan=plans[l],
                                          zero=zero))
                    new_hat[i][l] = _accumulate(h, (qs[i],), (1.0,))
                    hats[i][l] = h = None
            for i in range(m):
                js = [j for j in range(m) if W[i, j] > 0 and not (fault == "no_exchange" and j != i)]
                dst = (jnp.zeros_like(leaves[i][l]) if ss is None
                       else jax.device_put(ss[i][l], dev[i]))
                new_s[i][l] = _accumulate(dst, tuple(jax.device_put(qs[j], dev[i]) for j in js),
                                          tuple(float(W[i, j]) for j in js))
                if ss is not None:
                    ss[i][l] = None
            # hat and s wait on the host while the next round's gradients run
            for i in range(m):
                if probe:
                    hat_n[i, l], s_n[i, l] = (float(_norm(x)) for x in (new_hat[i][l], new_s[i][l]))
                new_hat[i][l], new_s[i][l] = jax.device_get((new_hat[i][l], new_s[i][l]))
            del qs
        theta = [jax.tree_util.tree_unflatten(treedef, x) for x in leaves]
        hat = [jax.tree_util.tree_unflatten(treedef, x) for x in new_hat]
        s = [jax.tree_util.tree_unflatten(treedef, x) for x in new_s]
        del leaves, hats, ss, new_hat, new_s
        if probe:
            ch = []
            for i in range(m):
                theta0 = make(jax.device_put(seed_key, dev[i]))
                ch.append(np.asarray(change_norms(theta[i], theta0)))
                del theta0
            read[f"change{tag}"] = np.stack(ch)
            read[f"hat{tag}"], read[f"s{tag}"] = hat_n, s_n
    return {"losses": np.asarray(losses), "grad_norms": gnorm, **read}
