"""Mesh-native neighbor-exchange gossip: the SPMD consensus substrate.

``core/gossip.py`` *simulates* the network on a single stacked array — every
mix is a ``jnp.roll`` over the full node axis or a dense ``[m, m]`` matmul,
and whether that turns into degree-many neighbor messages or an all-gather of
the whole stacked payload is left to GSPMD's sharding propagation.  This
module makes the wire model explicit: ``choco_round_ppermute`` runs the same
CHOCO round under ``jax.shard_map`` over the mesh's node axes,
where each device holds a contiguous block of nodes and *only compressed
payloads travel between actual graph neighbors* via ``jax.lax.ppermute``:

* circulant graphs (ring / torus / mesh) execute each shift of the
  :class:`~repro.core.topology.PermutePlan` as a global roll of the sharded
  node axis — at most two collective-permutes of boundary slabs per shift,
  independent of the nodes-per-device block size;
* irregular graphs (erdos_renyi, star, matching phases) execute the plan's
  :class:`~repro.core.topology.EdgeStep` barriers — per-edge partial
  permutations (one node per device required; see ROADMAP open items for the
  uneven-ratio generalization);
* time-varying schedules share ONE wire program — the
  :class:`~repro.core.wire.UnionWirePlan` union of all phases' exchange ops
  — whose per-phase mixing weights are gathered from banks by ``t % P``
  (one ``dynamic_index`` per round; the old per-mix-site ``lax.switch`` over
  whole phase programs is gone), and dropout-masked rounds compute the
  masked-Metropolis weights *locally from permuted participation bits*
  (alive bits travel the union's own exchanges, then degrees do) — no
  ``[m, m]`` matrix is ever materialized on the wire path;
* time-varying rounds run the memory-full CHOCO averaging against a
  **NeighborCache** — per-op mirrors of each in-neighbor's ``theta_hat``
  kept exact by the compressed hat-deltas that ride every union edge every
  round — so masked/scheduled rounds put only compressed payload bytes on
  the wire (the pre-refactor form shipped the f32 public copies).

Numerics: the static circulant paths (unpacked, packed, fused-Pallas)
replicate the rolled oracle's accumulation order operation-for-operation and
are bit-identical to ``gossip.choco_round`` jitted-vs-jitted; dense-matmul
oracle paths (irregular graphs, masked rounds) reassociate the neighbor sum
and agree to f32 rounding (~1 ULP per round) — tests/test_exchange.py pins
both levels.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.compression import Compressor, Identity
from repro.core.faults import (
    digest,
    garble,
    receiver_maps,
    sample_events,
    update_fault_state,
)
from repro.core.gossip import (
    BLOCK_SCAN_ELEMS,
    CHOCOState,
    LaneRound,
    _round_leaves,
    _scan_plan,
    _vdecode,
    lane_key,
    payload_total_bits,
)
from repro.core.topology import (
    PermutePlan,
    Topology,
    TopologySchedule,
    compile_permute_plan,
    compile_schedule_plans,
)

__all__ = [
    "choco_round_ppermute",
    "choco_round_ppermute_lanes",
    "choco_round_cached_local",
    "choco_round_cached_local_lanes",
    "mix_stacked_ppermute",
    "mix_stacked_faulted_local",
    "server_average_ppermute",
    "node_mesh_info",
]


def node_mesh_info(mesh, node_axes, num_nodes: int) -> tuple[tuple[str, ...], int, int]:
    """Validated (axes, ndev, block) for sharding ``num_nodes`` over the
    mesh's node axes.  ``block`` is the nodes-per-device contiguous block."""
    axes = (node_axes,) if isinstance(node_axes, str) else tuple(node_axes)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    missing = [a for a in axes if a not in sizes]
    if missing:
        raise ValueError(f"mesh {mesh.axis_names} has no axes {missing}")
    ndev = 1
    for a in axes:
        ndev *= int(sizes[a])
    if num_nodes % ndev != 0:
        raise ValueError(
            f"num_nodes={num_nodes} must be divisible by the node-axis device "
            f"count {ndev} (mesh axes {axes}); uneven node/device ratios are a "
            "ROADMAP open item"
        )
    return axes, ndev, num_nodes // ndev


def _flat_axis_index(axes: tuple[str, ...], sizes: dict[str, int]):
    """Row-major flat device index along the (possibly multi-axis) node dim."""
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * sizes[a] + jax.lax.axis_index(a)
    return idx


def _dev_perm(ndev: int, q: int) -> list[tuple[int, int]]:
    return [(i, (i + q) % ndev) for i in range(ndev)]


def _local_slice(arr, idx, block: int):
    """Device-local [block, ...] slice of a replicated [m, ...] array."""
    return jax.lax.dynamic_slice_in_dim(arr, idx * block, block, axis=0)


def _shard_roll(x, shift: int, axes, ndev: int, block: int):
    """``jnp.roll(x, shift, axis=0)`` over a node-sharded leading axis.

    Decomposes the global shift into a whole-block device permute plus one
    boundary-slab permute — the wire moves only what crosses a device
    boundary, so a ring shift of ±1 costs one node-row per device however
    many nodes a device hosts.  The shift is taken in the *minimal-|s|*
    signed representative: normalizing -1 to m-1 would turn the ring's
    backward edge into a full-block permute plus a (block-1)-row slab.
    """
    m = ndev * block
    s = shift % m
    if s == 0:
        return x
    if ndev == 1:
        return jnp.roll(x, s, axis=0)
    if s > m // 2:  # roll backward by m - s: fewer boundary rows on the wire
        b = m - s
        q, r = divmod(b, block)
        if q:
            x = jax.lax.ppermute(x, axes, _dev_perm(ndev, -q))
        if r:
            bot = jax.lax.ppermute(x[:r], axes, _dev_perm(ndev, -1))
            x = jnp.concatenate([x[r:], bot], axis=0)
        return x
    q, r = divmod(s, block)
    if q:
        x = jax.lax.ppermute(x, axes, _dev_perm(ndev, q))
    if r:
        top = jax.lax.ppermute(x[block - r :], axes, _dev_perm(ndev, 1))
        x = jnp.concatenate([top, x[: block - r]], axis=0)
    return x


def _recv(x, op, axes, ndev: int, block: int):
    """Receive the neighbor value for one plan exchange op.

    ``("shift", s)`` → global roll; ``("perm", pairs)`` → per-edge partial
    permutation (block == 1, node index == device index).  Nodes that
    receive nothing in a perm step get zeros — their receive weight is zero
    by construction.
    """
    kind, arg = op
    if kind == "shift":
        return _shard_roll(x, arg, axes, ndev, block)
    if ndev == 1:  # single-device degenerate mesh: permute rows locally
        out = jnp.zeros_like(x)
        for src, dst in arg:
            out = out.at[dst].set(x[src])
        return out
    return jax.lax.ppermute(x, axes, list(arg))


def _bcast(w, ndim: int):
    """[block] per-node weights broadcast against a [block, ...] leaf."""
    return w.reshape((w.shape[0],) + (1,) * (ndim - 1))


# ---------------------------------------------------------------- static mix
def _mix_local(x, plan: PermutePlan, axes, ndev, block, idx):
    """``sum_j w_ij x_j`` on the local shard — mirrors ``gossip._mix_leaf``.

    Circulant plans accumulate ``weight * shard_roll(x, shift)`` in the
    oracle's shift order (bit-identical); irregular plans accumulate the
    self term plus per-edge permutes (the dense-matmul oracle reassociated,
    ~1 ULP).
    """
    if plan.shifts is not None:
        out = jnp.zeros_like(x)
        for shift, weight in plan.shifts:
            term = x if shift == 0 else _shard_roll(x, shift, axes, ndev, block)
            out = out + weight * term
        return out
    wdt = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32
    xw = x.astype(wdt)
    sw = _local_slice(jnp.asarray(plan.self_weight, wdt), idx, block)
    out = _bcast(sw, x.ndim) * xw
    for step in plan.steps:
        w = _local_slice(jnp.asarray(step.weights, wdt), idx, block)
        out = out + _bcast(w, x.ndim) * _recv(xw, ("perm", step.perm), axes, ndev, block)
    return out.astype(x.dtype)


def _mix_payload_local(compressor, payload, shape, dtype, plan: PermutePlan,
                       axes, ndev, block, idx):
    """``sum_j w_ij decode(q_j)`` with the *packed payload* on the wire —
    mirrors ``gossip._mix_payload`` for circulant plans (bit-identical) and
    extends it to irregular plans (the rolled backend cannot pack those: it
    falls back to a dense mix of decoded tensors, all-gathering f32)."""
    troll = lambda p, op: jax.tree.map(lambda t: _recv(t, op, axes, ndev, block), p)
    if plan.shifts is not None:
        out = None
        for shift, weight in plan.shifts:
            rolled = payload if shift == 0 else troll(payload, ("shift", shift))
            deq = _vdecode(compressor, rolled, shape, dtype)
            out = weight * deq if out is None else out + weight * deq
        return out
    sw = _local_slice(jnp.asarray(plan.self_weight, jnp.float32), idx, block)
    out = _bcast(sw, len(shape) + 1) * _vdecode(compressor, payload, shape, dtype)
    for step in plan.steps:
        recv = troll(payload, ("perm", step.perm))
        deq = _vdecode(compressor, recv, shape, dtype)
        w = _local_slice(jnp.asarray(step.weights, jnp.float32), idx, block)
        out = out + _bcast(w, deq.ndim) * deq
    return out


# --------------------------------------------------- time-varying wire layer
# The union wire (repro.core.wire): every phase of a schedule shares ONE
# program — the deduplicated union of all phases' exchange ops — and the
# round's mixing weights come from per-phase banks gathered by t % P.  This
# replaces the old per-mix-site ``lax.switch`` over whole phase programs
# (ROADMAP phase-switch item: weights are now resolved ONCE per round, before
# the per-leaf loop) and enables the NeighborCache: because the compressed
# hat-delta travels every union edge every round, each device holds an exact
# mirror of every in-neighbor's theta_hat and the memory-full averaging step
# sum_j w_ij(t) theta_hat_j needs NOTHING on the wire — the f32 public-copy
# exchange the old masked round shipped is gone.


def _slice_bank(bank, phase, idx, block):
    """Per-phase bank [P, ..., m] -> phase row, local [..., block] slice."""
    row = bank[0] if bank.shape[0] == 1 else jax.lax.dynamic_index_in_dim(
        bank, phase, 0, keepdims=False
    )
    return jax.lax.dynamic_slice_in_dim(row, idx * block, block, axis=row.ndim - 1)


def _union_round_weights(union, phase, alive, masked: bool, axes, ndev, block,
                         idx, usable=None):
    """This round's wire weights, resolved once per round.

    Returns ``(self_w [block], ws list-of-[block], alive_nb list-or-None)``.
    Unmasked rounds read the static phase banks; masked rounds recompute
    masked-Metropolis weights locally from permuted participation bits (the
    distributed form of ``topology.masked_metropolis``, restricted to the
    phase's edges by the ``active`` bank): alive bits travel the union's own
    exchanges, per-node surviving degrees are summed on-device, then degrees
    travel the same exchanges to form w_ij = a_i a_j / (1 + max(deg_i,
    deg_j)).  ``alive_nb`` (each sender's participation bit, per op) is also
    what gates the receiver-side NeighborCache update.

    ``usable`` ([n_ops, block] f32, faulted wires) additionally masks each
    receiver's in-edges — an edge whose mirror diverged past the staleness
    bound is cut from the mix and its weight redistributed by the same
    surviving-subgraph rescale.  Usability is receiver-side knowledge, so
    under asymmetric faults W(t) is row- but not column-stochastic (the
    self-healing layer's documented bias/availability tradeoff; the digest
    layer bounds how long it persists).
    """
    ops = union.ops
    if not masked and usable is None:
        wb = _slice_bank(jnp.asarray(union.w_bank, jnp.float32), phase, idx, block)
        self_w = _slice_bank(jnp.asarray(union.self_bank, jnp.float32), phase, idx, block)
        return self_w, [wb[k] for k in range(len(ops))], None
    act = _slice_bank(jnp.asarray(union.active, jnp.float32), phase, idx, block)
    if usable is not None:
        act = act * usable
    alive_nb = [_recv(alive, op, axes, ndev, block) for op in ops]
    deg = jnp.zeros_like(alive)
    for k, nb in enumerate(alive_nb):
        deg = deg + act[k] * alive * nb
    deg_nb = [_recv(deg, op, axes, ndev, block) for op in ops]
    ws = [
        act[k] * alive * nb / (1.0 + jnp.maximum(deg, dnb))
        for k, (nb, dnb) in enumerate(zip(alive_nb, deg_nb))
    ]
    self_w = jnp.ones_like(alive)
    for w in ws:
        self_w = self_w - w
    return self_w, ws, alive_nb


def _phase_round_weights(union, p: int, alive, masked: bool, axes, ndev,
                         block, idx):
    """Phase-``p`` wire weights restricted to phase ``p``'s *active* ops —
    the literal-phase twin of :func:`_union_round_weights` used by the
    per-phase ``lax.switch`` branches of the dense-format mix.

    ``p`` is a Python int (each switch branch closes over its own phase), so
    the op subset and the weight rows are host-side constants: a branch
    exchanges only the edges its phase actually uses, which is what drops
    scheduled exact-gossip traffic from the union edge set to the active
    edge set (ROADMAP per-phase wire program item).  Numerics match the
    union path exactly — the ops skipped here carried weight 0.0 there.

    Returns ``(self_w [block], ws list-of-[block], ops)``.
    """
    act_np = np.asarray(union.active[p])  # [n_ops, m]
    ops_sel = [k for k in range(union.n_ops) if act_np[k].any()]
    ops = [union.ops[k] for k in ops_sel]
    loc = lambda row: jax.lax.dynamic_slice_in_dim(
        jnp.asarray(row, jnp.float32), idx * block, block
    )
    if not masked:
        self_w = loc(union.self_bank[p])
        return self_w, [loc(union.w_bank[p][k]) for k in ops_sel], ops
    act = [loc(act_np[k]) for k in ops_sel]
    alive_nb = [_recv(alive, op, axes, ndev, block) for op in ops]
    deg = jnp.zeros_like(alive)
    for a, nb in zip(act, alive_nb):
        deg = deg + a * alive * nb
    deg_nb = [_recv(deg, op, axes, ndev, block) for op in ops]
    ws = [
        a * alive * nb / (1.0 + jnp.maximum(deg, dnb))
        for a, nb, dnb in zip(act, alive_nb, deg_nb)
    ]
    self_w = jnp.ones_like(alive)
    for w in ws:
        self_w = self_w - w
    return self_w, ws, ops


def _weighted_mix(x, self_w, ws, ops, axes, ndev, block):
    """``sum_j w_ij(t) x_j`` in f32 with pre-resolved per-op weights — the
    dense-format union mix (exact consensus, lambda gossip)."""
    xf = x.astype(jnp.float32)
    out = _bcast(self_w, x.ndim) * xf
    for op, w in zip(ops, ws):
        out = out + _bcast(w, x.ndim) * _recv(xf, op, axes, ndev, block)
    return out


# ----------------------------------------------------------- faulted wire
def _inv_op(op):
    """The reverse exchange of a union op: moves a receiver-side value to its
    sender.  The resync-request lane — one ``want`` bit travels *against*
    each union edge so the sender knows to ship (and bill) the dense hat."""
    kind, arg = op
    if kind == "shift":
        return (kind, -arg)
    return (kind, tuple((d, s) for (s, d) in arg))


def _wire_msg_bits(compressor, theta_template, block_scan_elems):
    """Static per-message bit sizes on a faulted wire:
    ``(payload, digest, dense)``.

    ``payload`` — one compressed hat-delta for the whole tree (what every
    union edge carries every round); ``digest`` — 32 bits per leaf chunk (the
    chunking is ``_scan_plan``'s, so the lane is billed exactly as it is
    computed); ``dense`` — the full hat at its own dtype (the resync
    payload, shipped only on requested edges).
    """
    payload = payload_total_bits(compressor, theta_template)
    dense = dig = 0.0
    for leaf in jax.tree_util.tree_leaves(theta_template):
        d = int(np.prod(leaf.shape[1:])) if leaf.ndim > 1 else 1
        dense += float(d) * leaf.dtype.itemsize * 8.0
        plan = _scan_plan(leaf.shape, d, block_scan_elems)
        dig += 32.0 * (plan[1] if plan is not None else 1)
    return payload, dig, dense


class _FaultCtx(NamedTuple):
    """One round's resolved fault picture on the local node block: the
    receiver-side message gates (``[n_ops, block]``) plus the sender-side
    realized-bits meter (``[block]``).  One draw gates the whole message —
    the hat-delta, its digest, and any resync payload sharing the edge."""

    arrived: jax.Array  # bool: the message landed this round (vs drop/delay)
    corrupt: jax.Array  # bool: landed garbled — the digest will discard it
    want: jax.Array  # bool: receiver requests a full-hat resync this round
    bits: jax.Array  # f32: wire bits this node's own sends realize


def _fault_context(faults, fault_key, union, fstate, alive_local, alive_nb,
                   msg_bits, axes, ndev, block, idx, m):
    """Sample the round's message events and resolve them into receiver-side
    gates and sender-side billing.

    Events are drawn on the *global* ``[n_ops, m]`` edge set from the
    replicated fault key, so every device (and both backends, and a test
    reconstructing ground truth) classifies the same draw identically; each
    device then slices its receiver block.  Faults only exist on live edges:
    a slot with no sender (``senders[k][i] < 0``) or a masked-out sender
    carries no message to fault — its ``arrived`` is vacuously True so the
    recovery state machine never ages an edge that had nothing to deliver.

    Billing is *delivered* bits, credited to the sender: drops bill zero,
    duplicates twice, corrupt/late deliveries once (the bytes moved; the
    digest just refuses to apply them).  Receiver-indexed event lanes reach
    the sender through the static receiver maps — no wire traffic to meter
    the wire — while the ``want`` bit travels the reverse exchange, and a
    requested resync adds the dense hat to that edge's message.
    """
    ev = sample_events(faults, fault_key, union.n_ops, m)
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, idx * block, block, axis=1)
    exist = sl(jnp.asarray(
        np.stack([np.asarray(s) >= 0 for s in union.senders]), bool
    ))
    live = exist
    if alive_nb is not None:
        live = live & (jnp.stack(alive_nb) > 0.0)
    arrived = jnp.where(live, ~(sl(ev.drop) | sl(ev.delay)), True)
    corrupt = sl(ev.corrupt) & live
    want = live & (fstate.stale.T > faults.stale) & (fstate.wait.T <= 0)
    payload_b, digest_b, dense_b = msg_bits
    mult = jnp.where(ev.drop, 0.0, jnp.where(ev.dup, 2.0, 1.0))
    bits = jnp.zeros((block,), jnp.float32)
    for k, (op, rcv) in enumerate(zip(union.ops, receiver_maps(union))):
        rcv_l = _local_slice(jnp.asarray(rcv, jnp.int32), idx, block)
        mult_k = jnp.where(rcv_l >= 0, mult[k][jnp.clip(rcv_l, 0)], 0.0)
        want_sent = _recv(
            want[k].astype(jnp.float32), _inv_op(op), axes, ndev, block
        )
        bits = bits + mult_k * (payload_b + digest_b + want_sent * dense_b)
    return _FaultCtx(arrived, corrupt, want, bits * alive_local)


# ------------------------------------------------------------- leaf rounds
def _round_leaf_local(leaf, hat, s, key, plan, gamma, compressor: Compressor,
                      use_packed, use_fused, axes, ndev, block, idx, m_global):
    """One static CHOCO round on the local node block — mirrors
    ``gossip._round_leaf`` operation-for-operation."""
    if use_fused:
        return _fused_round_local(
            leaf, hat, s, key, plan, gamma, compressor, axes, ndev, block, idx, m_global
        )
    inner_shape, dtype = leaf.shape[1:], leaf.dtype
    theta_new = leaf + jnp.asarray(gamma, dtype) * (s - hat).astype(dtype)
    resid = (theta_new - hat).astype(jnp.float32)
    if isinstance(compressor, Identity):
        q_self = resid
        mixed = _mix_local(q_self, plan, axes, ndev, block, idx)
    else:
        node_keys = _local_slice(jax.random.split(key, m_global), idx, block)
        payload = jax.vmap(compressor.encode)(resid, node_keys)
        q_self = _vdecode(compressor, payload, inner_shape, jnp.float32)
        if use_packed:
            mixed = _mix_payload_local(
                compressor, payload, inner_shape, jnp.float32, plan,
                axes, ndev, block, idx,
            )
        else:
            mixed = _mix_local(q_self, plan, axes, ndev, block, idx)
    hat_new = (hat.astype(jnp.float32) + q_self).astype(hat.dtype)
    s_new = (s.astype(jnp.float32) + mixed).astype(s.dtype)
    return theta_new, hat_new, s_new


def _fused_round_local(leaf, hat, s, key, plan, gamma, compressor,
                       axes, ndev, block, idx, m_global):
    """Single-pass Pallas fast path on the local shard: the fused encode /
    multi-shift dequant-accumulate kernels run on the [block, ...] slab and
    the packed payload travels the wire via :func:`_shard_roll`."""
    from repro.kernels.ops import fused_choco_round_leaf

    node_keys = _local_slice(jax.random.split(key, m_global), idx, block)
    roll_fn = lambda x, sh: _shard_roll(x, sh, axes, ndev, block)
    return fused_choco_round_leaf(
        leaf, hat, s, key, plan, gamma, compressor.bits,
        getattr(compressor, "interpret", None),
        roll_fn=roll_fn, node_keys=node_keys,
    )


def _round_leaf_cached(leaf, hat, s, key, caches, union, weights, gamma,
                       compressor: Compressor, alive, masked: bool,
                       use_payload: bool, axes, ndev, block, idx, m_global,
                       fctx=None):
    """Time-varying / fault-tolerant round on the local block — the
    memory-full CHOCO form of ``gossip._round_leaf_masked`` executed against
    the NeighborCache: the averaging step ``sum_j w_ij(t) theta_hat_j`` reads
    each in-neighbor's hat from its local mirror (``caches``, one per union
    op) instead of shipping f32 public copies, and the only model-sized wire
    traffic is the compressed hat-delta payload — which each receiver both
    mixes into ``s`` and applies to its mirror with the *same arithmetic the
    sender applies to its own hat*, keeping every mirror bit-identical to the
    sender's ``theta_hat`` (the invariant tests/test_wire_cache.py pins).

    Dropped senders contribute a zero delta (their residual is masked before
    encode) and the alive bit riding each exchange gates the mirror update,
    so a mirror of a dead neighbor freezes exactly like the neighbor's own
    hat does.

    ``fctx`` (a :class:`_FaultCtx`) switches the wire to the faulted regime:
    corrupt messages are garbled in flight, the sender's hat digest rides
    every message, and the receiver verifies ``digest(mirror + delta)``
    against it *before* committing — a missing or garbled delta leaves the
    mirror untouched (and out of this round's ``s`` increment, so the
    tracker stays consistent with what the mirrors actually did).  A
    requested resync ships the sender's post-round hat dense on the same
    message, subject to the same draw.  Returns a fifth element, the
    ``[2, n_ops, block]`` (delta-ok, resync-ok) verdict for this chunk.
    """
    self_w, ws, alive_nb = weights
    inner_shape, dtype = leaf.shape[1:], leaf.dtype
    hat32 = hat.astype(jnp.float32)
    ab = _bcast(alive, leaf.ndim)
    # averaging from cached neighbor hats — nothing on the wire
    s_cur = _bcast(self_w, leaf.ndim) * hat32
    for w, c in zip(ws, caches):
        s_cur = s_cur + _bcast(w, leaf.ndim) * c.astype(jnp.float32)
    theta_new = leaf + (ab * gamma).astype(dtype) * (s_cur - hat32).astype(dtype)
    resid = ((theta_new - hat).astype(jnp.float32)) * ab
    payload = None
    if isinstance(compressor, Identity):
        q_self = resid
    else:
        node_keys = _local_slice(jax.random.split(key, m_global), idx, block)
        payload = jax.vmap(compressor.encode)(resid, node_keys)
        q_self = _vdecode(compressor, payload, inner_shape, jnp.float32) * ab
    hat_new = (hat32 + q_self).astype(hat.dtype)
    dig_self = digest(hat_new) if fctx is not None else None
    # the wire: one compressed hat-delta per union op (decode commutes with
    # the permute, so decode-after-receive == receive-after-decode bitwise)
    mix_q = _bcast(self_w, leaf.ndim) * q_self
    new_caches = []
    d_oks, r_oks = [], []
    for k, op in enumerate(union.ops):
        if use_payload and payload is not None:
            recv_p = jax.tree.map(
                lambda t: _recv(t, op, axes, ndev, block), payload
            )
            q_r = _vdecode(compressor, recv_p, inner_shape, jnp.float32)
        else:
            q_r = _recv(q_self, op, axes, ndev, block)
        if masked:
            q_r = q_r * _bcast(alive_nb[k], leaf.ndim)
        if fctx is None:
            new_caches.append(
                (caches[k].astype(jnp.float32) + q_r).astype(caches[k].dtype)
            )
            mix_q = mix_q + _bcast(ws[k], leaf.ndim) * q_r
            continue
        cb = _bcast(fctx.corrupt[k], leaf.ndim)
        q_r = jnp.where(cb, garble(q_r), q_r)
        cand = (caches[k].astype(jnp.float32) + q_r).astype(caches[k].dtype)
        dig_nb = _recv(dig_self, op, axes, ndev, block)
        ok_d = fctx.arrived[k] & (digest(cand) == dig_nb)
        hat_recv = _recv(hat_new, op, axes, ndev, block)
        hat_recv = jnp.where(cb, garble(hat_recv), hat_recv)
        ok_r = fctx.want[k] & fctx.arrived[k] & (digest(hat_recv) == dig_nb)
        okd_b, okr_b = _bcast(ok_d, leaf.ndim), _bcast(ok_r, leaf.ndim)
        new_caches.append(
            jnp.where(okr_b, hat_recv, jnp.where(okd_b, cand, caches[k]))
        )
        # only committed deltas enter the tracker increment (a jnp.where,
        # not a multiply — a garbled q_r may carry NaN bit patterns)
        mix_q = mix_q + _bcast(ws[k], leaf.ndim) * jnp.where(okd_b, q_r, 0.0)
        d_oks.append(ok_d)
        r_oks.append(ok_r)
    s_post = s_cur + mix_q
    s_new = (ab * s_post + (1.0 - ab) * s.astype(jnp.float32)).astype(s.dtype)
    if fctx is None:
        return theta_new, hat_new, s_new, tuple(new_caches)
    verdict = jnp.stack([jnp.stack(d_oks), jnp.stack(r_oks)])
    return theta_new, hat_new, s_new, tuple(new_caches), verdict


# ------------------------------------------------------------------- rounds
def _cached_round_body(theta, st, key, alive, step_arg, fault_key, *, union,
                       gamma, compressor, use_packed, masked, faults,
                       msg_bits, axes, ndev, block, idx, m,
                       block_scan_elems):
    """One cached union-wire round on a local node block — the body both
    backends execute: ``choco_round_ppermute`` shard_maps it over the mesh's
    node axes; ``choco_round_cached_local`` runs it with the whole node axis
    as one block (``ndev == 1``).  Sharing the body makes rolled/ppermute
    bit-parity under faults *structural* rather than something numerics have
    to deliver."""
    lv, td = jax.tree_util.tree_flatten(theta)
    hv = td.flatten_up_to(st.theta_hat)
    sv = td.flatten_up_to(st.s)
    keys = jax.random.split(key, len(lv))
    alive_local = (
        jnp.ones((block,), jnp.float32) if alive is None
        else alive.astype(jnp.float32)
    )
    phase = (
        jnp.zeros((), jnp.int32) if union.period == 1
        else step_arg % union.period
    )
    fstate = st.fault
    usable = None
    if faults is not None:
        # an edge past the staleness bound leaves the mix (its weight
        # redistributes by the surviving-subgraph rescale) until resync lands
        usable = (fstate.stale.T <= faults.stale).astype(jnp.float32)
    # the round's mixing weights, resolved ONCE — not per leaf, not per mix
    # site, and with no lax.switch over phase programs
    weights = _union_round_weights(
        union, phase, alive_local, masked, axes, ndev, block, idx, usable
    )
    fctx = None
    if faults is not None:
        fctx = _fault_context(
            faults, fault_key, union, fstate, alive_local, weights[2],
            msg_bits, axes, ndev, block, idx, m,
        )
    cache_lv = [td.flatten_up_to(c) for c in st.cache]
    extra = [
        tuple(cache_lv[k][i] for k in range(union.n_ops))
        for i in range(len(lv))
    ]

    def round_one(leaf, hat, s, k, caches):
        return _round_leaf_cached(
            leaf, hat, s, k, caches, union, weights, gamma, compressor,
            alive_local, masked, use_packed, axes, ndev, block, idx, m,
            fctx=fctx,
        )

    verdict_init = (
        jnp.ones((2, union.n_ops, block), bool) if faults is not None else None
    )
    # the chunk layout and per-chunk key stream come from the SAME driver
    # as the static rolled backend — bit-parity across backends is structural
    new_theta, new_hat, new_s, new_extra, verdict = _round_leaves(
        lv, hv, sv, keys, round_one, block_scan_elems,
        extra_leaves=extra, verdict_init=verdict_init,
    )
    unf = lambda ls: jax.tree_util.tree_unflatten(td, ls)
    cache_new = tuple(
        unf([new_extra[i][k] for i in range(len(lv))])
        for k in range(union.n_ops)
    )
    fault_new = fstate
    if faults is not None:
        fault_new = update_fault_state(
            fstate, verdict[0], verdict[1], fctx.want, faults, fctx.bits
        )
    return unf(new_theta), CHOCOState(
        theta_hat=unf(new_hat), s=unf(new_s), cache=cache_new,
        fault=fault_new,
    )


def _check_fault_state(state, faults, fault_key, union):
    if faults is None:
        return
    if fault_key is None:
        raise ValueError(
            "faulted rounds need the round's fault_key — one PRNG key per "
            "round, split from the trainer's per-step stream so kill-and-"
            "resume replays the same events"
        )
    if (not hasattr(state.fault, "stale")
            or state.fault.stale.shape[-1] != union.n_ops):
        raise ValueError(
            "faulted rounds keep a per-edge FaultState in CHOCOState.fault "
            f"(need one for {union.n_ops} union ops) — initialize the state "
            "with gossip.choco_init(theta, cache_ops=n, fault_ops=n) or let "
            "trainer.ChocoConsensus.init size it from the fault spec"
        )


def choco_round_ppermute(
    theta_half,
    state: CHOCOState,
    topology: Topology,
    gamma: float,
    compressor: Compressor,
    key: jax.Array,
    *,
    mesh,
    node_axes="data",
    packed: bool = True,
    fused: bool = False,
    block_scan_elems: int = BLOCK_SCAN_ELEMS,
    schedule: TopologySchedule | None = None,
    step=None,
    mask=None,
    union=None,
    faults=None,
    fault_key=None,
):
    """One compressed-consensus round on the SPMD neighbor-exchange backend.

    Drop-in for ``gossip.choco_round`` (reached via its ``backend="ppermute"``
    dispatch): same state threading, same RNG stream, same scan-plan leaf
    chunking — but executed under ``shard_map`` over ``mesh``'s
    ``node_axes``, with only compressed payloads on the wire: the static
    packed/fused formats, or (time-varying rounds) the hat-delta format
    applied against the NeighborCache.

    ``schedule`` + ``step`` + ``mask`` replace the rolled backend's dense
    ``mixing`` argument: all phases compile into ONE
    :class:`~repro.core.wire.UnionWirePlan` wire program whose per-phase
    mixing weights are gathered by ``step % P`` (no ``lax.switch``), and a
    participation mask triggers the locally-computed masked-Metropolis
    weights.  Time-varying rounds require the state's NeighborCache (one
    ``theta_hat`` mirror per union op, allocated by
    ``gossip.choco_init(theta, cache_ops=...)`` /
    ``trainer.ChocoConsensus.init``): the averaging step reads the cached
    mirrors and only the compressed hat-delta payload travels the wire.

    ``faults`` (a :class:`~repro.core.faults.FaultSpec`) + ``fault_key``
    switch the wire to the faulted regime — always the cached union path,
    even for a static topology, because only the NeighborCache form has a
    mirror to verify and heal.
    """
    thetas, states = choco_round_ppermute_lanes(
        (LaneRound(theta_half, state, gamma, compressor),), topology, key,
        mesh=mesh, node_axes=node_axes, packed=packed, fused=fused,
        block_scan_elems=block_scan_elems, schedule=schedule, step=step,
        mask=mask, union=union, faults=faults, fault_key=fault_key,
    )
    return thetas[0], states[0]


def choco_round_ppermute_lanes(
    lanes,
    topology: Topology,
    key: jax.Array,
    *,
    mesh,
    node_axes="data",
    packed: bool = True,
    fused: bool = False,
    block_scan_elems: int = BLOCK_SCAN_ELEMS,
    schedule: TopologySchedule | None = None,
    step=None,
    mask=None,
    union=None,
    faults=None,
    fault_key=None,
):
    """The multi-lane SPMD round: every edge of the round's wire program
    carries a *tuple* of messages, one per :class:`~repro.core.gossip.LaneRound`.

    All lanes run inside ONE ``shard_map`` body, so the per-edge message
    really is the lane tuple — the same ops of the same round move lane 0's
    payload and lane 1's payload together (XLA is free to coalesce the
    adjacent collective-permutes).  Each lane keeps its own compressed
    residual stream (lane ``k``'s RNG is ``lane_key(key, k)``), its own
    NeighborCache mirrors, and — under faults — its own per-edge event draws,
    digests and recovery state: a corrupted lane-1 message stales only lane
    1's mirror, never the theta mirror.  A single-lane call (what
    :func:`choco_round_ppermute` delegates to) is bit-identical to the
    historical single-payload wire because lane 0's keys are the round keys
    themselves.

    Returns ``(thetas, states)`` tuples, one entry per lane.
    """
    from repro.core.wire import compile_union_wire

    lanes = tuple(lanes)
    n_lanes = len(lanes)
    leaves = jax.tree_util.tree_leaves(lanes[0].theta)
    m = leaves[0].shape[0]
    axes, ndev, block = node_mesh_info(mesh, node_axes, m)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    time_varying = (
        (schedule is not None and not getattr(schedule, "is_static", True))
        or mask is not None
        or faults is not None
    )
    if time_varying:
        if union is None:
            # standalone use; the consensus layer passes its precompiled
            # plan (the same one that sized the state's cache) instead
            if schedule is not None:
                plans = compile_schedule_plans(schedule)
            else:
                plans = (compile_permute_plan(topology),)
            union = compile_union_wire(plans)
        _check_block(any(k == "perm" for k, _ in union.ops), block, ndev)
        use_packed = [
            packed and not isinstance(l.compressor, Identity) for l in lanes
        ]
        use_fused = [False] * n_lanes
        plan = None
        for li, l in enumerate(lanes):
            if len(l.state.cache) != union.n_ops:
                raise ValueError(
                    "time-varying ppermute rounds keep a NeighborCache (one "
                    f"theta_hat mirror per union wire op; lane {li} needs "
                    f"{union.n_ops}, has {len(l.state.cache)}) — initialize "
                    "each lane's state with gossip.choco_init(theta, "
                    "cache_ops=n) or let the consensus init size it"
                )
            _check_fault_state(l.state, faults, fault_key, union)
    else:
        plan = compile_permute_plan(topology)
        _check_block(not plan.is_circulant, block, ndev)
        union = None
        use_packed = [
            packed and not isinstance(l.compressor, Identity) for l in lanes
        ]
        use_fused = [
            fused
            and plan.is_circulant
            and getattr(l.compressor, "supports_fused_round", False)
            for l in lanes
        ]

    masked = mask is not None
    faulted = faults is not None
    msg_bits = [
        _wire_msg_bits(l.compressor, l.theta, block_scan_elems) if faulted
        else None
        for l in lanes
    ]
    args = [*(l.theta for l in lanes), *(l.state for l in lanes), key]
    specs = [P(axes)] * (2 * n_lanes) + [P()]
    if masked:
        args.append(mask)
        specs.append(P(axes))
    if time_varying:
        step_arr = jnp.zeros((), jnp.int32) if step is None else jnp.asarray(step, jnp.int32)
        args.append(step_arr)
        specs.append(P())
    if faulted:
        args.append(fault_key)
        specs.append(P())

    def body(*sharded):
        rest = list(sharded)
        thetas = [rest.pop(0) for _ in range(n_lanes)]
        sts = [rest.pop(0) for _ in range(n_lanes)]
        key_ = rest.pop(0)
        alive = rest.pop(0) if masked else None
        step_arg = rest.pop(0) if time_varying else None
        fkey = rest.pop(0) if faulted else None
        idx = _flat_axis_index(axes, sizes)

        out_t, out_s = [], []
        for li, lane in enumerate(lanes):
            lk = lane_key(key_, li)
            lfk = lane_key(fkey, li)
            if time_varying:
                t_new, s_new = _cached_round_body(
                    thetas[li], sts[li], lk, alive, step_arg, lfk,
                    union=union, gamma=lane.gamma, compressor=lane.compressor,
                    use_packed=use_packed[li], masked=masked, faults=faults,
                    msg_bits=msg_bits[li], axes=axes, ndev=ndev, block=block,
                    idx=idx, m=m, block_scan_elems=block_scan_elems,
                )
            else:
                lv, td = jax.tree_util.tree_flatten(thetas[li])
                hv = td.flatten_up_to(sts[li].theta_hat)
                sv = td.flatten_up_to(sts[li].s)
                keys = jax.random.split(lk, len(lv))

                def round_one(leaf, hat, s, k, lane=lane, li=li):
                    return _round_leaf_local(
                        leaf, hat, s, k, plan, lane.gamma, lane.compressor,
                        use_packed[li], use_fused[li], axes, ndev, block,
                        idx, m,
                    )

                # the chunk layout and per-chunk key stream come from the
                # SAME driver as the rolled backend — bit-parity of the two
                # is structural
                new_theta, new_hat, new_s, _, _ = _round_leaves(
                    lv, hv, sv, keys, round_one, block_scan_elems
                )
                unf = lambda ls, td=td: jax.tree_util.tree_unflatten(td, ls)
                t_new = unf(new_theta)
                s_new = CHOCOState(
                    theta_hat=unf(new_hat), s=unf(new_s),
                    cache=sts[li].cache, fault=sts[li].fault,
                )
            out_t.append(t_new)
            out_s.append(s_new)
        return tuple(out_t), tuple(out_s)

    out_specs = ((P(axes),) * n_lanes, (P(axes),) * n_lanes)
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(specs), out_specs=out_specs,
        check_vma=False,
    )
    return fn(*args)


def choco_round_cached_local(
    theta_half,
    state: CHOCOState,
    gamma: float,
    compressor: Compressor,
    key: jax.Array,
    *,
    union=None,
    packed: bool = True,
    block_scan_elems: int = BLOCK_SCAN_ELEMS,
    schedule: TopologySchedule | None = None,
    topology: Topology | None = None,
    step=None,
    mask=None,
    faults=None,
    fault_key=None,
):
    """The cached union-wire round without a mesh: the whole node axis is one
    local block (``ndev == 1``), every exchange a local roll/permute.  This
    is how the rolled backend (``gossip.choco_round``) runs faulted rounds —
    the *same* ``_cached_round_body`` the ppermute backend shard_maps, so the
    two backends agree bit-for-bit under faults by construction."""
    thetas, states = choco_round_cached_local_lanes(
        (LaneRound(theta_half, state, gamma, compressor),), key, union=union,
        packed=packed, block_scan_elems=block_scan_elems, schedule=schedule,
        topology=topology, step=step, mask=mask, faults=faults,
        fault_key=fault_key,
    )
    return thetas[0], states[0]


def choco_round_cached_local_lanes(
    lanes,
    key: jax.Array,
    *,
    union=None,
    packed: bool = True,
    block_scan_elems: int = BLOCK_SCAN_ELEMS,
    schedule: TopologySchedule | None = None,
    topology: Topology | None = None,
    step=None,
    mask=None,
    faults=None,
    fault_key=None,
):
    """Multi-lane cached union-wire round without a mesh — the rolled twin of
    :func:`choco_round_ppermute_lanes`, sharing its per-lane key folding and
    the per-lane ``_cached_round_body``, so rolled/ppermute bit-parity holds
    lane-by-lane under faults by construction.  Returns ``(thetas, states)``
    tuples, one entry per lane."""
    from repro.core.wire import compile_union_wire

    lanes = tuple(lanes)
    leaves = jax.tree_util.tree_leaves(lanes[0].theta)
    m = leaves[0].shape[0]
    if union is None:
        if schedule is not None:
            plans = compile_schedule_plans(schedule)
        else:
            plans = (compile_permute_plan(topology),)
        union = compile_union_wire(plans)
    for li, l in enumerate(lanes):
        if len(l.state.cache) != union.n_ops:
            raise ValueError(
                "cached union-wire rounds keep a NeighborCache (one theta_hat "
                f"mirror per union wire op; lane {li} needs {union.n_ops}, "
                f"has {len(l.state.cache)}) — initialize each lane's state "
                "with gossip.choco_init(theta, cache_ops=n) or let the "
                "consensus init size it from the schedule"
            )
        _check_fault_state(l.state, faults, fault_key, union)
    step_arr = jnp.zeros((), jnp.int32) if step is None else jnp.asarray(step, jnp.int32)
    out_t, out_s = [], []
    for li, lane in enumerate(lanes):
        msg_bits = (
            _wire_msg_bits(lane.compressor, lane.theta, block_scan_elems)
            if faults is not None else None
        )
        t_new, s_new = _cached_round_body(
            lane.theta, lane.state, lane_key(key, li), mask, step_arr,
            lane_key(fault_key, li), union=union, gamma=lane.gamma,
            compressor=lane.compressor,
            use_packed=packed and not isinstance(lane.compressor, Identity),
            masked=mask is not None, faults=faults, msg_bits=msg_bits,
            axes=(), ndev=1, block=m, idx=0, m=m,
            block_scan_elems=block_scan_elems,
        )
        out_t.append(t_new)
        out_s.append(s_new)
    return tuple(out_t), tuple(out_s)


def _dense_msg_bits(tree) -> float:
    """Bits of one dense-format message (the whole tree at leaf dtype) plus
    its 32-bit-per-leaf digest lane — what a faulted memoryless mix bills
    per delivered edge."""
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        d = int(np.prod(leaf.shape[1:])) if leaf.ndim > 1 else 1
        total += float(d) * leaf.dtype.itemsize * 8.0 + 32.0
    return total


def _memoryless_fault(faults, fault_key, union, dense_msg, axes, ndev, block,
                      idx, m):
    """Memoryless fault resolution for the dense-format union mix (exact
    consensus, lambda gossip): there is no mirror to heal, so a message that
    dropped / garbled / arrived late simply leaves this round's mix — the
    digest vets delivery, the masked-Metropolis rescale redistributes the
    weight, and next round the edge is fresh again.  Returns
    ``(usable [n_ops, block] f32, bits [block] f32)`` — usability for the
    weight recompute, delivered bits (dup bills twice) for the meter."""
    ev = sample_events(faults, fault_key, union.n_ops, m)
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, idx * block, block, axis=1)
    usable = sl(~(ev.drop | ev.corrupt | ev.delay)).astype(jnp.float32)
    mult = jnp.where(ev.drop, 0.0, jnp.where(ev.dup, 2.0, 1.0))
    bits = jnp.zeros((block,), jnp.float32)
    for k, rcv in enumerate(receiver_maps(union)):
        rcv_l = _local_slice(jnp.asarray(rcv, jnp.int32), idx, block)
        bits = bits + jnp.where(rcv_l >= 0, mult[k][jnp.clip(rcv_l, 0)], 0.0)
    return usable, bits * dense_msg


def mix_stacked_ppermute(tree, topology: Topology, *, mesh, node_axes="data",
                         schedule: TopologySchedule | None = None,
                         step=None, mask=None, union=None,
                         faults=None, fault_key=None):
    """Uncompressed (dense-format) gossip mix of a stacked pytree over the
    neighbor-exchange wire — the SPMD counterpart of ``gossip.mix_stacked``
    / ``mix_stacked_with``.  The dual/lambda gossip and
    :class:`~repro.core.trainer.ExactConsensus` ride exactly these permutes
    when the ppermute backend is on; ``schedule``/``step``/``mask`` select
    the round's weights from the union wire's per-phase banks (dense [m, m]
    matrices never exist on this path — dropped nodes degenerate to the
    identity row locally, exactly like ``masked_metropolis``).

    ``faults`` + ``fault_key`` run the memoryless faulted regime (see
    :func:`_memoryless_fault`); the call then returns ``(mixed, bits)`` with
    ``bits`` the [m] per-sender delivered-bits meter."""
    leaves = jax.tree_util.tree_leaves(tree)
    m = leaves[0].shape[0]
    axes, ndev, block = node_mesh_info(mesh, node_axes, m)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    time_varying = (
        (schedule is not None and not getattr(schedule, "is_static", True))
        or mask is not None
        or faults is not None
    )
    if not time_varying:
        plan = compile_permute_plan(topology)
        _check_block(not plan.is_circulant, block, ndev)

        def body(t):
            idx = _flat_axis_index(axes, sizes)
            return jax.tree.map(
                lambda x: _mix_local(x, plan, axes, ndev, block, idx), t
            )

        return jax.shard_map(
            body, mesh=mesh, in_specs=P(axes), out_specs=P(axes), check_vma=False
        )(tree)

    from repro.core.wire import compile_union_wire

    if union is None:
        if schedule is not None:
            plans = compile_schedule_plans(schedule)
        else:
            plans = (compile_permute_plan(topology),)
        union = compile_union_wire(plans)
    _check_block(any(k == "perm" for k, _ in union.ops), block, ndev)
    masked = mask is not None
    faulted = faults is not None
    if faulted and fault_key is None:
        raise ValueError("faulted mixes need the round's fault_key")
    dense_msg = _dense_msg_bits(tree) if faulted else 0.0

    args = [tree]
    specs = [P(axes)]
    if masked:
        args.append(mask)
        specs.append(P(axes))
    step_arr = jnp.zeros((), jnp.int32) if step is None else jnp.asarray(step, jnp.int32)
    args.append(step_arr)
    specs.append(P())
    if faulted:
        args.append(fault_key)
        specs.append(P())

    def body_tv(t, *rest):
        rest = list(rest)
        alive = rest.pop(0) if masked else None
        step_arg = rest.pop(0)
        fkey = rest.pop(0) if faulted else None
        idx = _flat_axis_index(axes, sizes)
        alive_local = (
            jnp.ones((block,), jnp.float32) if alive is None
            else alive.astype(jnp.float32)
        )
        phase = (
            jnp.zeros((), jnp.int32) if union.period == 1
            else step_arg % union.period
        )
        usable, bits = None, None
        if faulted:
            usable, bits = _memoryless_fault(
                faults, fkey, union, dense_msg, axes, ndev, block, idx, m
            )
            bits = bits * alive_local
        if union.period > 1 and not faulted:
            # per-phase wire program: one lax.switch over phase branches,
            # each exchanging only its phase's active edges — scheduled
            # dense-format traffic drops from the union edge set to the
            # active set.  Faulted mixes stay on the union path: the event
            # draw is indexed per union op and the masked rescale needs the
            # usable bits of every op.
            def make_branch(p):
                def branch(operand):
                    t_, alive_ = operand
                    self_w, ws, ops = _phase_round_weights(
                        union, p, alive_, masked, axes, ndev, block, idx
                    )
                    return jax.tree.map(
                        lambda x: _weighted_mix(
                            x, self_w, ws, ops, axes, ndev, block
                        ).astype(x.dtype),
                        t_,
                    )
                return branch

            return jax.lax.switch(
                phase, [make_branch(p) for p in range(union.period)],
                (t, alive_local),
            )
        self_w, ws, _ = _union_round_weights(
            union, phase, alive_local, masked, axes, ndev, block, idx, usable
        )
        mixed = jax.tree.map(
            lambda x: _weighted_mix(
                x, self_w, ws, union.ops, axes, ndev, block
            ).astype(x.dtype),
            t,
        )
        return (mixed, bits) if faulted else mixed

    out_specs = (P(axes), P(axes)) if faulted else P(axes)
    return jax.shard_map(
        body_tv, mesh=mesh, in_specs=tuple(specs), out_specs=out_specs,
        check_vma=False,
    )(*args)


def mix_stacked_faulted_local(tree, *, union=None, topology=None,
                              schedule=None, step=None, mask=None,
                              faults, fault_key):
    """The memoryless faulted mix without a mesh (rolled backend): the whole
    node axis is one local block, same code path as the ppermute body — the
    two agree bit-for-bit by construction.  Returns ``(mixed, bits)``."""
    from repro.core.wire import compile_union_wire

    leaves = jax.tree_util.tree_leaves(tree)
    m = leaves[0].shape[0]
    if union is None:
        if schedule is not None:
            plans = compile_schedule_plans(schedule)
        else:
            plans = (compile_permute_plan(topology),)
        union = compile_union_wire(plans)
    if fault_key is None:
        raise ValueError("faulted mixes need the round's fault_key")
    alive = (
        jnp.ones((m,), jnp.float32) if mask is None
        else mask.astype(jnp.float32)
    )
    step_arr = jnp.zeros((), jnp.int32) if step is None else jnp.asarray(step, jnp.int32)
    phase = (
        jnp.zeros((), jnp.int32) if union.period == 1
        else step_arr % union.period
    )
    usable, bits = _memoryless_fault(
        faults, fault_key, union, _dense_msg_bits(tree), (), 1, m, 0, m
    )
    bits = bits * alive
    self_w, ws, _ = _union_round_weights(
        union, phase, alive, mask is not None, (), 1, m, 0, usable
    )
    mixed = jax.tree.map(
        lambda x: _weighted_mix(x, self_w, ws, union.ops, (), 1, m).astype(x.dtype),
        tree,
    )
    return mixed, bits


def server_average_ppermute(tree, sampled, *, mesh, node_axes="data"):
    """Weighted server average of a stacked pytree — the mesh-native wire of
    :class:`~repro.core.trainer.FedAvg`.  Each device reduces its local node
    block, then one ``psum`` over the node axes aggregates and re-broadcasts:
    the ring all-reduce realization of "|U| models up, one model down", with
    zero all-gather traffic (the rolled form's ``sum(0)`` of the stacked
    array lets GSPMD all-gather the whole model stack instead).  Output is
    replicated (no node axis)."""
    leaves = jax.tree_util.tree_leaves(tree)
    m = leaves[0].shape[0]
    axes, ndev, block = node_mesh_info(mesh, node_axes, m)

    def body(t, sm):
        sm = sm.astype(jnp.float32)
        wsum = jax.lax.psum(sm.sum(), axes)

        def avg(x):
            part = (x.astype(jnp.float32) * _bcast(sm, x.ndim)).sum(0)
            return (jax.lax.psum(part, axes) / wsum).astype(x.dtype)

        return jax.tree.map(avg, t)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(axes), P(axes)), out_specs=P(),
        check_vma=False,
    )(tree, sampled)


def _check_block(irregular: bool, block: int, ndev: int) -> None:
    """Irregular (non-circulant) wire programs need one node per device: a
    perm/EdgeStep exchange is a *device* permutation.  A single-device mesh
    is exempt — there is no wire, and ``_recv`` executes the node
    permutation locally."""
    if ndev > 1 and block > 1 and irregular:
        raise ValueError(
            "the ppermute backend runs irregular (non-circulant) graphs with "
            "exactly one node per device; got a block of "
            f"{block} nodes/device — use the rolled backend or a mesh whose "
            "node axes match num_nodes (uneven ratios: ROADMAP open item)"
        )
