"""Composable decentralized-DRO trainer (paper Algorithms 1-2 as one loop).

AD-GDA, CHOCO-SGD, DR-DSGD (Issaid et al. 2022) and DRFA (Deng et al. 2021)
are all the same round — local update, dual update, communication — differing
only in which instance fills each slot.  This module factors the training
layer into three small protocols and one driver:

* :class:`LocalUpdate` — the stochastic oracle (single-step, microbatched
  gradient accumulation, or K local steps between communication rounds) with
  parameter updates routed through :class:`repro.optim.Optimizer` and a
  :data:`repro.optim.Schedule` (SGD/momentum/Nesterov/Adam, const/exp/cosine
  + warmup — no hand-rolled SGD in the algorithms anymore);
* :class:`DualUpdate` — how the mixture weights lambda evolve: projected
  ascent with gossip (AD-GDA), the KL closed form (DR-DSGD), frozen at the
  prior (CHOCO-SGD), or sampled ascent on observed losses (DRFA);
* :class:`Consensus` — how models travel the wire: the CHOCO compressed
  round (with the ``packed``/``fused`` Pallas dispatch), exact mixing, or
  federated server averaging.

:class:`DecentralizedTrainer` composes the three and owns the round
skeleton: RNG bookkeeping, the running average of the network mean
(theta_o, Thm 4.1), aux metrics and bits accounting.  The paper's named
algorithms are one-line factories over it — see ``repro.core.adgda`` and
``repro.core.baselines`` — and new combinations (Adam-based AD-GDA, local
steps with momentum, robust federated averaging over a ring, ...) are
compositions, not new classes.

All decentralized state is *stacked*: every pytree leaf carries a leading
node axis of size m, which the production mesh shards over ``data`` (x
``pod``) so the vmapped oracle is plain data parallelism.  How the
consensus maps to collectives is the exchange *backend*'s choice:
``backend="rolled"`` (default) simulates the network on the stacked array
and leaves the lowering to GSPMD, ``backend="ppermute"`` executes it
mesh-native — shard_map + ``lax.ppermute`` moving exactly degree-many
compressed messages between graph neighbors (``repro.core.exchange``).
Federated consensus (:class:`FedAvg`) instead keeps a single server model
in the state and broadcasts it to the node axis at the start of each round.

Numerics are pinned to the pre-refactor monolithic trainers bit-for-bit on
the single-step and microbatched paths (tests/test_trainer_parity.py); the
local-steps path applies the dual weighting before the learning rate (the
seed multiplied in the opposite order) and is pinned to ~ULP instead.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dro
from repro.core.compression import Compressor, Identity
from repro.core import wire
from repro.core.faults import WireBits, parse_fault_spec
from repro.core.gossip import (
    BLOCK_SCAN_ELEMS,
    CHOCOState,
    LaneRound,
    _scan_plan,
    choco_init,
    choco_round,
    choco_round_lanes,
    mix_stacked,
    mix_stacked_with,
    payload_bits,
    payload_total_bits,
)
from repro.core.topology import (
    Topology,
    TopologySchedule,
    compile_permute_plan,
    compile_schedule_plans,
)
from repro.optim import Optimizer, OptState, Schedule

__all__ = [
    "LossFn",
    "TrainerState",
    "LocalUpdate",
    "DualUpdate",
    "ProjectedAscent",
    "FrozenPrior",
    "KLClosedForm",
    "SampledAscent",
    "Consensus",
    "ChocoConsensus",
    "GTState",
    "GradientTrackingConsensus",
    "ExactConsensus",
    "FedAvg",
    "DecentralizedTrainer",
]

LossFn = Callable[[Any, Any, jax.Array], jax.Array]


class TrainerState(NamedTuple):
    step: jax.Array  # round counter
    theta: Any  # stacked pytree [m, ...] (federated: server pytree, no node axis)
    lam: jax.Array  # dual variable: [m, m] decentralized copies or [m] server-side
    opt: OptState  # optimizer moments + its own step counter
    consensus: Any  # CHOCOState or () — whatever Consensus.init returned
    theta_avg: Any  # running mean over time of the network mean (theta_o)
    rng: jax.Array


def _apply_updates(params, updates):
    """p <- p + u in f32, cast back to the parameter dtype."""
    return jax.tree.map(
        lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype), params, updates
    )


def _scale_grads(grads, scale: jax.Array, m: int):
    """Per-node dual weighting: g_i <- lam-weight_i * g_i (in f32)."""
    return jax.tree.map(
        lambda g: g.astype(jnp.float32) * scale.reshape((m,) + (1,) * (g.ndim - 1)),
        grads,
    )


def _select_nodes(mask: jax.Array, new_tree, old_tree, m: int):
    """Per-node select: keep ``new`` where mask==1, revert to ``old`` where a
    node sat the round out.  Applied leaf-wise to stacked trees; leaves
    without a leading node axis (e.g. the optimizer's scalar step counter,
    which is per-*round*, not per-node) keep the new value."""
    alive = mask > 0
    def sel(new, old):
        if getattr(new, "ndim", 0) >= 1 and new.shape[0] == m:
            return jnp.where(alive.reshape((m,) + (1,) * (new.ndim - 1)), new, old)
        return new
    return jax.tree.map(sel, new_tree, old_tree)


# ============================================================== local update
@dataclasses.dataclass(frozen=True)
class LocalUpdate:
    """Stochastic oracle + optimizer step on the stacked model.

    One of three shapes, all sharing the dual weighting and the optimizer:

    * ``microbatches == local_steps == 1`` — one vmapped value-and-grad and
      one optimizer update per round;
    * ``microbatches = k > 1`` — gradient accumulation: scan the oracle over
      k microbatches so only one microbatch's activations are live at a
      time, then one optimizer update (same stochastic gradient);
    * ``local_steps = K > 1`` — K full optimizer updates between
      communication rounds (paper §6's event-triggered extension).  The
      optimizer state (momentum, Adam moments) carries across the inner
      steps AND across rounds; the schedule and Adam bias correction are
      evaluated once per *round* (the optimizer's step counter advances by
      one per round regardless of K), matching the seed trainers' per-round
      learning-rate decay.

    ``batch_layout`` fixes how K local batches arrive: ``"flat"`` packs them
    along the per-node batch axis (leaves ``[m, K*b, ...]``, AD-GDA style),
    ``"stacked"`` gives them a dedicated axis (leaves ``[m, K, ...]``, DRFA
    style).
    """

    optimizer: Optimizer
    schedule: Schedule
    microbatches: int = 1
    local_steps: int = 1
    grad_accum_dtype: str = "float32"
    spmd_axis_name: Any = None  # mesh axes the node vmap maps to
    batch_layout: str = "flat"

    def __post_init__(self):
        if self.local_steps > 1 and self.microbatches > 1:
            raise ValueError("local_steps and microbatches do not compose")
        if self.batch_layout not in ("flat", "stacked"):
            raise ValueError(f"unknown batch_layout {self.batch_layout!r}")

    def init(self, theta_stacked) -> OptState:
        return self.optimizer.init(theta_stacked)

    def lr(self, opt_state: OptState) -> jax.Array:
        return self.schedule(opt_state.step)

    def _oracle(self, loss_fn, theta, batch, node_keys):
        return jax.vmap(
            jax.value_and_grad(loss_fn), spmd_axis_name=self.spmd_axis_name
        )(theta, batch, node_keys)

    def step(self, loss_fn: LossFn, theta, opt_state: OptState, batch, node_keys,
             weights_fn: Callable[[jax.Array], jax.Array]):
        """Run the oracle + optimizer; returns (theta_half, opt_state, losses).

        ``weights_fn(losses) -> [m]`` supplies the dual gradient weighting
        (called after every loss evaluation, so closed-form duals see the
        freshest losses).
        """
        m = node_keys.shape[0]

        if self.local_steps > 1:
            return self._local_steps(loss_fn, theta, opt_state, batch, node_keys,
                                     weights_fn, m)
        if self.microbatches > 1:
            losses, grads = self._microbatched(loss_fn, theta, batch, node_keys, m)
        else:
            losses, grads = self._oracle(loss_fn, theta, batch, node_keys)

        scale = weights_fn(losses)
        updates, opt_state = self.optimizer.update(
            _scale_grads(grads, scale, m), opt_state, theta
        )
        return _apply_updates(theta, updates), opt_state, losses

    # -------------------------------------------------- gradient accumulation
    def _microbatched(self, loss_fn, theta, batch, node_keys, m):
        k = self.microbatches
        acc_dt = jnp.dtype(self.grad_accum_dtype)

        def to_mb(leaf):  # [m, b, ...] -> [k, m, b/k, ...]
            assert leaf.shape[1] % k == 0, (
                f"per-node batch {leaf.shape[1]} not divisible by microbatches {k}"
            )
            return leaf.reshape((m, k, leaf.shape[1] // k) + leaf.shape[2:]).swapaxes(0, 1)

        mb = jax.tree.map(to_mb, batch)

        def body(carry, mbatch):
            acc_l, acc_g = carry
            l, g = self._oracle(loss_fn, theta, mbatch, node_keys)
            acc_g = jax.tree.map(lambda a, gg: a + (gg.astype(acc_dt) / k), acc_g, g)
            return (acc_l + l / k, acc_g), None

        zeros_g = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), theta)
        (losses, grads), _ = jax.lax.scan(
            body, (jnp.zeros((m,), jnp.float32), zeros_g), mb
        )
        return losses, grads

    # ------------------------------------------------------- K local steps
    def _local_steps(self, loss_fn, theta, opt_state, batch, node_keys, weights_fn, m):
        K = self.local_steps
        if self.batch_layout == "stacked":  # [m, K, ...] -> [K, m, ...]
            kb = jax.tree.map(lambda x: x.swapaxes(0, 1), batch)
        else:

            def to_k(leaf):  # [m, K*b, ...] -> [K, m, b, ...]
                assert leaf.shape[1] % K == 0, (
                    f"per-node batch {leaf.shape[1]} not divisible by local_steps {K}"
                )
                return leaf.reshape((m, K, leaf.shape[1] // K) + leaf.shape[2:]).swapaxes(0, 1)

            kb = jax.tree.map(to_k, batch)

        round_step = opt_state.step

        def body(carry, mbatch):
            theta, ostate = carry
            l, g = self._oracle(loss_fn, theta, mbatch, node_keys)
            scale = weights_fn(l)
            updates, ostate = self.optimizer.update(_scale_grads(g, scale, m), ostate, theta)
            # schedule / Adam bias correction are per-round: every inner step
            # sees the round's step count, bumped once after the scan
            ostate = ostate._replace(step=round_step)
            return (_apply_updates(theta, updates), ostate), l

        (theta, opt_state), losses_k = jax.lax.scan(body, (theta, opt_state), kb)
        return theta, opt_state._replace(step=round_step + 1), losses_k.mean(0)


# ================================================================ dual update
class DualUpdate:
    """How the mixture weights lambda evolve across rounds.

    ``grad_weights`` is the per-node scaling the oracle applies to gradients
    (lambda_i / pi_i so that lambda == prior recovers plain SGD, paper
    §5.2.2); ``update`` advances lambda after the oracle using the observed
    per-node losses.  ``begin`` lets a dual draw per-round randomness
    (DRFA's client sampling) and share it with the consensus via ``ctx``.
    """

    needs_key: bool = False

    def init(self, m: int) -> jax.Array:
        raise NotImplementedError

    def begin(self, lam: jax.Array, key: jax.Array | None):
        return None

    def grad_weights(self, lam: jax.Array, losses: jax.Array) -> jax.Array:
        m = losses.shape[0]
        return jnp.ones((m,), jnp.float32)

    def update(self, lam: jax.Array, losses: jax.Array, ctx, *,
               mixing: jax.Array | None = None,
               mask: jax.Array | None = None,
               step=None, fault_key=None) -> jax.Array:
        """Advance lambda.  Under a time-varying/fault-tolerant consensus the
        trainer passes the round index ``step``, the participation ``mask``,
        and — on the rolled backend only — the round's dense ``mixing``
        matrix, so dual gossip travels the same wire as the model (the
        ppermute backend has no dense matrix: the dual rides the union-wire
        ``mix_fn`` instead); duals that don't gossip ignore them.
        ``fault_key`` is the round's message-fault key when a FaultSpec is
        active — the lambda gossip rides the *same* physical messages as the
        model, so it sees the same event draw."""
        raise NotImplementedError

    def bits_per_round(self) -> float:
        return 0.0


@dataclasses.dataclass(frozen=True)
class ProjectedAscent(DualUpdate):
    """AD-GDA's dual: projected gradient ascent + uncompressed lambda gossip.

    Every node keeps its own copy of lambda (state [m, m]); the round is

        lam_i <- sum_j w_ij P_simplex(lam_j + eta_lam (f_j e_j + alpha grad r))

    The lambda gossip is uncompressed — m floats per neighbor, negligible
    next to the model payload but accounted in :meth:`bits_per_round`.

    ``mix_fn`` overrides how the lambda gossip travels: the factories set it
    to the consensus's :meth:`ChocoConsensus.wire_mix` when the ppermute
    backend is on, so the dual rides the same neighbor permutes as the model
    instead of a stacked-array roll — including time-varying rounds, where
    ``wire_mix`` selects the round's weights from the union wire's banks via
    ``step``/``mask``.  (Rolled time-varying rounds receive the dense W(t)
    from the trainer instead — lambda is [m, m], wire cost negligible.)
    """

    prior: jax.Array
    alpha: float
    eta_lambda: float
    regularizer: dro.Regularizer
    topology: Topology
    mix_fn: Callable | None = None

    def init(self, m: int) -> jax.Array:
        return jnp.broadcast_to(self.prior[None], (m, m)).copy()

    def grad_weights(self, lam, losses):
        return (jnp.diagonal(lam) / self.prior).astype(jnp.float32)

    def update(self, lam, losses, ctx, *, mixing=None, mask=None, step=None,
               fault_key=None):
        m = lam.shape[0]
        node_ids = jnp.arange(m)
        dual_grads = jax.vmap(
            lambda f, i, l: dro.dual_gradient(
                f, i, l, self.prior, self.alpha, self.regularizer
            )
        )(losses, node_ids, lam)
        lam_half = jax.vmap(dro.project_simplex)(lam + self.eta_lambda * dual_grads)
        if mask is not None:  # dropped nodes skip their local ascent step too
            lam_half = jnp.where((mask > 0).reshape((m, 1)), lam_half, lam)
        if mixing is not None:
            return mix_stacked_with(lam_half, mixing)
        if self.mix_fn is not None:
            return self.mix_fn(lam_half, step=step, mask=mask,
                               fault_key=fault_key)
        return mix_stacked(lam_half, self.topology)

    def bits_per_round(self) -> float:
        return 32.0 * int(self.prior.shape[0]) * self.topology.max_degree


@dataclasses.dataclass(frozen=True)
class FrozenPrior(DualUpdate):
    """Non-robust baseline (CHOCO-SGD): lambda frozen at the prior."""

    prior: jax.Array

    def init(self, m: int) -> jax.Array:
        return jnp.broadcast_to(self.prior[None], (m, m)).copy()

    def update(self, lam, losses, ctx, **_):
        return lam


@dataclasses.dataclass(frozen=True)
class KLClosedForm(DualUpdate):
    """DR-DSGD's dual: the KL inner max in closed form, lambda_i ∝ pi_i e^{f_i/alpha}.

    No ascent state to carry — lambda is recomputed from the current losses
    every round (state [m], kept for logging).  The normalizer is one scalar
    all-reduce per round (32 bits; accounting difference vs. gossiping it is
    nil, see baselines module docstring).
    """

    prior: jax.Array
    alpha: float

    def init(self, m: int) -> jax.Array:
        return jnp.asarray(self.prior)

    def grad_weights(self, lam, losses):
        w = dro.kl_closed_form_weights(losses, self.prior, self.alpha)
        return (w / self.prior).astype(jnp.float32)

    def update(self, lam, losses, ctx, **_):
        return dro.kl_closed_form_weights(losses, self.prior, self.alpha)


@dataclasses.dataclass(frozen=True)
class SampledAscent(DualUpdate):
    """DRFA's dual: sample |U| clients ~ lambda (Gumbel top-k, no replacement),
    run the round on them, then projected ascent on the importance-corrected
    observed losses.  The sampling mask is shared with :class:`FedAvg`
    through the round ``ctx``."""

    prior: jax.Array
    eta_lambda: float
    local_steps: int
    num_sampled: int

    needs_key = True

    def init(self, m: int) -> jax.Array:
        return jnp.asarray(self.prior)

    def begin(self, lam, key):
        m = lam.shape[0]
        gumbel = -jnp.log(-jnp.log(jax.random.uniform(key, (m,)) + 1e-20) + 1e-20)
        scores = jnp.log(lam + 1e-20) + gumbel
        _, sampled = jax.lax.top_k(scores, self.num_sampled)
        return jnp.zeros((m,), jnp.float32).at[sampled].set(1.0)

    def update(self, lam, losses, ctx, **_):
        sampled = ctx  # the begin() sampling mask, shared with FedAvg
        m = lam.shape[0]
        wsum = sampled.sum()
        loss_vec = losses * sampled * (m / jnp.maximum(wsum, 1.0))
        return dro.project_simplex(lam + self.eta_lambda * self.local_steps * loss_vec)


# ================================================================== consensus
class Consensus:
    """How the half-step models travel the wire.

    ``schedule`` is non-None when the wire is time-varying (a
    :class:`~repro.core.topology.TopologySchedule` with period > 1 and/or
    node dropout); the trainer then threads the round index, the
    participation ``mask`` and the round's dense ``mixing`` matrix into
    :meth:`mix`.  Static consensus implementations ignore them.

    ``backend`` names the exchange implementation the consensus executes on:
    ``"rolled"`` (the stacked-array simulation — rolls / dense matmuls over
    the full node axis) or ``"ppermute"`` (the mesh-native SPMD substrate of
    ``core/exchange.py`` — shard_map + lax.ppermute moving only degree-many
    compressed messages between graph neighbors).
    """

    needs_key: bool = False
    federated: bool = False  # True -> state.theta has no node axis
    schedule: TopologySchedule | None = None
    backend: str = "rolled"

    def init(self, theta_stacked):
        return ()

    def mix(self, theta_half, state, key: jax.Array | None, ctx, *,
            step=None, mask=None, mixing=None, fault_key=None,
            theta_prev=None):
        """Mix the half-step models.  ``theta_prev`` is the round's
        pre-local-update theta (what the trainer held before the oracle ran)
        — gradient-tracking consensus reads the local displacement from it;
        every other implementation ignores it."""
        raise NotImplementedError

    @property
    def wire_format(self) -> wire.WireFormat:
        """Byte format of one per-edge message (see repro.core.wire)."""
        return wire.DENSE

    def bits_per_round(self, theta_template, *, mode: str = "max",
                       step=None, mask=None) -> float:
        """Busiest-node bits per round.  ``mode``: "max" (upper bound,
        default), "expected" (participation-aware phase average), or
        "realized" (actual links of round ``step`` under ``mask``)."""
        raise NotImplementedError

    def bits_realized(self, theta_template, step, mask, consensus_state=None):
        """This round's realized wire bits as a *traced* scalar — the jitted
        form of ``bits_per_round(mode="realized")`` the trainer threads into
        ``aux["bits_realized"]`` so long faulty runs report measured traffic
        without host-side masks.  ``consensus_state`` is the *post-mix*
        consensus state: faulted wires carry an in-graph per-node bits meter
        there (delivered bits only — dropped messages are not billed, dups
        bill twice, resyncs bill their dense payload).  Default: the
        max-degree constant (exact for static full-participation wires)."""
        return jnp.float32(self.bits_per_round(theta_template, mode="max"))


def _resolve_wire_backend(backend: str, mesh, schedule, topology=None, faults=None):
    """Shared ctor validation for the ``backend`` knob: checks the name,
    requires a mesh for ppermute, and compiles the union wire program when
    the wire is time-varying — or when a fault model is active, since fault
    injection lives at the exchange boundary and runs every backend through
    the cached union round body (one plan per consensus instance — the same
    object then sizes the NeighborCache + FaultState, selects round weights,
    and bills bits, so they cannot drift)."""
    if backend not in ("rolled", "ppermute"):
        raise ValueError(f"unknown gossip backend {backend!r}; choose rolled or ppermute")
    if backend == "ppermute" and mesh is None:
        raise ValueError("backend='ppermute' requires a mesh (see launch.mesh.make_node_mesh)")
    needs_union = (backend == "ppermute" and schedule is not None) or faults is not None
    if not needs_union:
        return None
    if schedule is not None:
        return wire.compile_union_wire(
            compile_schedule_plans(schedule), name=schedule.name
        )
    if topology is None:
        raise ValueError("fault injection needs a topology or schedule to compile the wire")
    return wire.compile_union_wire((compile_permute_plan(topology),))


def _union_degree(union, schedule, mode: str, mask) -> float:
    """Billing degree of the union wire: every union edge carries one
    message every round, dropped only when the sender itself is dead (a
    dead receiver's messages are deferred re-sync traffic, not avoided)."""
    if mode == "max":
        return float(union.max_out_degree)
    if mode == "expected":
        rate = schedule.dropout_rate if schedule is not None else 0.0
        return union.max_out_degree * (1.0 - rate)
    if mode == "realized":
        if mask is None:
            raise ValueError("mode='realized' needs the round's participation mask")
        return union.realized_out_degree(mask)
    raise ValueError(f"unknown bits mode {mode!r}; choose max/expected/realized")


def _fault_bits_meter(cons_state):
    """The faulted wire's in-graph per-node bits meter, if ``cons_state``
    carries one: CHOCO keeps it in ``CHOCOState.fault.bits``, the memoryless
    exact wire in a bare :class:`~repro.core.faults.WireBits`, and a
    multi-lane :class:`GTState` sums its lanes' meters (each lane billed its
    own deliveries).  None when the state has no meter (fault-free run, or
    pre-round state)."""
    if hasattr(cons_state, "model") and hasattr(cons_state, "tracker"):
        a = _fault_bits_meter(cons_state.model)
        b = _fault_bits_meter(cons_state.tracker)
        if a is not None and b is not None:
            return a + b
        return None
    fault = getattr(cons_state, "fault", None)
    if hasattr(fault, "bits"):
        return fault.bits
    if hasattr(cons_state, "bits") and not hasattr(cons_state, "theta_hat"):
        return cons_state.bits
    return None


def _split_schedule(topology):
    """Normalize a Topology-or-Schedule ctor arg.

    Returns (representative_topology, schedule_or_None, gamma_source): static
    schedules unwrap to their phase topology so the circulant fast paths (and
    bit-identical numerics) are preserved; time-varying ones keep phase 0 as
    the representative for introspection and use the schedule's worst phase
    for step-size theory.
    """
    if isinstance(topology, TopologySchedule):
        sched = None if topology.is_static else topology
        return topology.topology_at(0), sched, (sched or topology.topology_at(0))
    return topology, None, topology


class ChocoConsensus(Consensus):
    """CHOCO-GOSSIP compressed round (Koloskova et al. 2019) with the
    ``packed`` (mix encoded payload) / ``fused`` (single-pass Pallas,
    kernels/choco_fused.py) dispatch preserved from ``gossip.choco_round``.

    Constructed with a plain :class:`Topology` or a
    :class:`TopologySchedule`; with a time-varying schedule the round mixes
    with the schedule's dense W(t) (packed/fused dispatch does not apply —
    the wire pattern changes every round) and honors the participation mask.
    """

    needs_key = True

    def __init__(self, topology: Topology | TopologySchedule, compressor: Compressor,
                 gamma: float | str | None = None, *, packed: bool = True,
                 fused: bool = False, backend: str = "rolled", mesh=None,
                 node_axes="data", faults=None):
        self.topology, self.schedule, self._gamma_topology = _split_schedule(topology)
        self.compressor = compressor
        self.gamma_spec = gamma
        self.packed = packed
        self.fused = fused
        self.backend = backend
        self.mesh = mesh
        self.node_axes = node_axes
        # the message-fault model (None = perfect wire); faults force the
        # cached union wire on every backend — detection and recovery live
        # at the exchange boundary (see repro.core.faults)
        self.faults = parse_fault_spec(faults)
        # the time-varying ppermute wire: one union program for every phase,
        # and a NeighborCache sized to its op count (see repro.core.wire)
        self.union = _resolve_wire_backend(
            backend, mesh, self.schedule, topology=self.topology, faults=self.faults
        )
        # provisional gamma until init()/mix() see the real leaf sizes
        self.gamma = self._resolve_gamma(4096)

    @staticmethod
    def _encode_dim(theta) -> int:
        """Largest per-node encode size the gossip layer will actually run on
        a *stacked* pytree — the dimension the compressor's contraction
        factor delta depends on.  Mirrors ``gossip._scan_plan``'s chunking
        exactly (a chunk can exceed BLOCK_SCAN_ELEMS when the leaf has no
        suitable divisor, or the whole leaf is encoded when no plan exists)."""
        best = 1
        for leaf in jax.tree_util.tree_leaves(theta):
            inner = int(np.prod(leaf.shape[1:])) if leaf.ndim > 1 else 1
            plan = _scan_plan(leaf.shape, inner, BLOCK_SCAN_ELEMS)
            best = max(best, inner if plan is None else inner // plan[1])
        return best

    def _resolve_gamma(self, d: int) -> float:
        """Consensus step size gamma for the largest single encode of size d.

        Gamma trades consensus speed against compression-noise injection; the
        right value scales with the compressor's contraction factor delta,
        which for quantization depends on the dimension d being compressed
        (delta = 1/tau, tau = 1 + min(d/2^2b, sqrt(d)/2^b) — paper eq. (2)).
        Resolution order:

        * ``gamma == "theory"`` — the Theorem 4.1 value: provably convergent
          but very conservative in practice;
        * a number — used verbatim (the paper grid-searches gamma per
          compression level, §5.1.1);
        * ``None`` — 0.5 * delta(d), a robust default across our experiments.

        Called with a 4096-element placeholder at construction, then from
        ``init()`` and again at every ``mix()`` trace with the actual pytree's
        leaf shapes — the compressor contracts *leaf-wise* (and the gossip
        layer chunks leaves above BLOCK_SCAN_ELEMS), so the dimension that
        matters is the largest single encode, not the total parameter count.
        """
        delta = getattr(self.compressor, "delta", 1.0)
        if hasattr(self.compressor, "delta_for"):
            delta = self.compressor.delta_for(max(int(d), 1))
        if self.gamma_spec == "theory":
            # worst (smallest-gap) phase when the topology is a schedule
            return self._gamma_topology.consensus_step_size(max(delta, 1e-3))
        if self.gamma_spec is not None:
            return float(self.gamma_spec)
        return 0.5 * max(delta, 1e-3)

    def init(self, theta_stacked) -> CHOCOState:
        # keep ``.gamma`` introspectable for the actual model; mix() re-resolves
        # at trace time so a step traced without init() still gets the right value
        self.gamma = self._resolve_gamma(self._encode_dim(theta_stacked))
        return choco_init(
            theta_stacked,
            cache_ops=self.union.n_ops if self.union is not None else 0,
            fault_ops=self.union.n_ops if self.faults is not None else None,
        )

    def mix(self, theta_half, state, key, ctx, *, step=None, mask=None,
            mixing=None, fault_key=None, theta_prev=None):
        gamma = self._resolve_gamma(self._encode_dim(theta_half))
        if self.backend == "ppermute":
            # the SPMD substrate takes the schedule + round index + mask and
            # compiles its own per-phase wire programs — a dense W(t) has no
            # wire meaning there
            return choco_round(
                theta_half, state, self.topology, gamma, self.compressor, key,
                packed=self.packed, fused=self.fused, mask=mask,
                backend="ppermute", mesh=self.mesh, node_axes=self.node_axes,
                schedule=self.schedule, step=step, union=self.union,
                faults=self.faults, fault_key=fault_key,
            )
        if self.faults is not None:
            # faulted rolled wire: the cached union round (same body as the
            # ppermute backend with a single full-width shard) — a dense
            # W(t) cannot express per-edge delivery faults
            return choco_round(
                theta_half, state, self.topology, gamma, self.compressor, key,
                packed=self.packed, mask=mask, schedule=self.schedule,
                step=step, union=self.union, faults=self.faults,
                fault_key=fault_key,
            )
        if self.schedule is not None and mixing is None:
            # standalone use (no trainer threading): resolve W(t) here
            mixing = self.schedule.mixing_at(0 if step is None else step, mask)
        return choco_round(
            theta_half, state, self.topology, gamma, self.compressor, key,
            packed=self.packed, fused=self.fused, mixing=mixing, mask=mask,
        )

    def wire_mix(self, tree, *, step=None, mask=None, fault_key=None):
        """Uncompressed (dense-format) gossip of a stacked tree over this
        consensus's wire — the dual/lambda gossip rides the same permutes as
        the model on the ppermute backend.  Time-varying rounds select their
        weights from the union wire's per-phase banks via ``step``/``mask``;
        the rolled backend's time-varying duals get the dense W(t) from the
        trainer instead and never reach here (unless faults are active, which
        force the union wire on every backend).  Under faults the dual rides
        the *same* physical messages as the model — same ``fault_key``, same
        event draw — and its delivered bits stay billed at the existing
        constant (negligible next to the model payload)."""
        if self.backend == "ppermute":
            from repro.core.exchange import mix_stacked_ppermute

            out = mix_stacked_ppermute(
                tree, self.topology, mesh=self.mesh, node_axes=self.node_axes,
                schedule=self.schedule, step=step, mask=mask, union=self.union,
                faults=self.faults, fault_key=fault_key,
            )
            return out[0] if self.faults is not None else out
        if self.faults is not None:
            from repro.core.exchange import mix_stacked_faulted_local

            mixed, _ = mix_stacked_faulted_local(
                tree, union=self.union, topology=self.topology,
                schedule=self.schedule, step=step, mask=mask,
                faults=self.faults, fault_key=fault_key,
            )
            return mixed
        return mix_stacked(tree, self.topology)

    @property
    def wire_format(self) -> wire.WireFormat:
        if isinstance(self.compressor, Identity) or not self.packed:
            return wire.DENSE
        return wire.HAT_DELTA if self.union is not None else wire.PAYLOAD

    def bits_per_round(self, theta_template, *, mode: str = "max",
                       step=None, mask=None, compressor=None) -> float:
        comp = compressor if compressor is not None else self.compressor
        if self.union is not None:
            # cached union wire: every union edge carries one hat-delta
            # payload every round (that is what keeps the mirrors exact), so
            # the honest degree is the union out-degree
            return payload_bits(
                comp, theta_template, self.schedule,
                degree=_union_degree(self.union, self.schedule, mode, mask),
            )
        return payload_bits(
            comp, theta_template, self.schedule or self.topology,
            mode=mode, step=step, mask=mask,
        )

    def bits_per_lane(self, theta_template, *, mode: str = "max",
                      step=None, mask=None) -> dict:
        """Per-lane busiest-node bits: one entry per :attr:`wire_format`
        lane, keyed by lane name.  Every lane of a multi-lane CHOCO wire
        carries the same compressed shape over the same edges, so each lane
        bills the single-lane cost; the round total is the sum."""
        one = ChocoConsensus.bits_per_round(
            self, theta_template, mode=mode, step=step, mask=mask
        )
        return {lane.name: one for lane in self.wire_format}

    def bits_realized(self, theta_template, step, mask, consensus_state=None):
        if self.faults is not None:
            meter = _fault_bits_meter(consensus_state)
            if meter is not None:
                # the exchange's own delivered-bits meter: drops unbilled,
                # dups billed twice, resyncs bill their dense payload
                return meter.max()
        total = payload_total_bits(self.compressor, theta_template)
        if self.union is not None:
            return total * self.union.realized_out_degree_traced(mask)
        if self.schedule is not None:
            return total * self.schedule.realized_degree_traced(step, mask)
        return total * self.topology.realized_degree_traced(step, mask)


class GTState(NamedTuple):
    """Gradient-tracking consensus state: one :class:`CHOCOState` per wire
    lane (the model lane and the tracker lane each keep their own hat/s,
    NeighborCache mirrors and fault-recovery machine), plus the tracker
    variable ``y`` — each node's gossiped estimate of the network-average
    local displacement — and ``d_prev``, the node's own displacement from
    the previous round it participated in."""

    model: CHOCOState
    tracker: CHOCOState
    y: Any  # stacked pytree [m, ...], theta-shaped
    d_prev: Any  # stacked pytree [m, ...], theta-shaped


def _gt_bcast(mask, leaf):
    """[m] participation mask broadcast against a [m, ...] leaf (f32)."""
    return mask.astype(jnp.float32).reshape(
        (mask.shape[0],) + (1,) * (leaf.ndim - 1)
    )


class GradientTrackingConsensus(ChocoConsensus):
    """CHOCO-compressed gossip with gradient tracking for K local steps
    (Robust Decentralized Learning with Local Updates and Gradient Tracking,
    arXiv 2405.00965, in CHOCO displacement form).

    Plain local SGD drifts under heterogeneous data: between gossip rounds
    each node descends toward its *local* optimum, and with large K the
    compressed gossip equilibrium is biased.  Gradient tracking gossips a
    second variable ``y`` that tracks the network-average local
    displacement; each node then moves by the tracked average instead of its
    own displacement, so heterogeneous nodes take many local steps without
    client drift.  One round, with ``d_i = theta_half_i - theta_prev_i`` the
    node's K-step displacement::

        y_half_i = y_i + d_i - d_prev_i            # tracker update
        x_half_i = theta_prev_i + y_half_i         # drift-corrected iterate
        theta    <- CHOCO-round(x_half, model lane)
        y        <- CHOCO-round(y_half, tracker lane)
        d_prev_i <- d_i

    Both CHOCO rounds ride the *same* wire round as a two-lane message
    (:func:`~repro.core.gossip.choco_round_lanes`): lane 0 is the model
    hat-delta with the historical key stream, lane 1 the tracker hat-delta
    keyed by ``fold_in(key, 1)``.  Each lane keeps its own NeighborCache and
    fault state, so a corrupted tracker message can never poison a theta
    mirror.  Mean trajectories are preserved (``mean(y_t) ==
    mean(d_{t-1})`` by induction; doubly-stochastic mixing keeps both lane
    means), so with K=1 the dynamics match plain CHOCO local-SGD in the
    network mean while individual nodes stay consensus-anchored.

    ``tracker=False`` disables the second lane entirely and delegates every
    code path to :class:`ChocoConsensus` — bit-identical on both backends
    (the K=1 parity anchor the tests pin).

    Dropped nodes (participation mask 0) freeze ``y`` and ``d_prev`` along
    with their CHOCO trackers: the trainer reverts their theta_half, so
    ``d_i = 0``, and the update above is gated per node — a node rejoins
    with a consistent tracker.
    """

    def __init__(self, topology: Topology | TopologySchedule,
                 compressor: Compressor, gamma: float | str | None = None, *,
                 tracker: bool = True, tracker_gamma: float | None = None,
                 tracker_compressor: Compressor | str | None = None,
                 **kw):
        super().__init__(topology, compressor, gamma, **kw)
        self.tracker = tracker
        self.tracker_gamma_spec = tracker_gamma
        # the tracker lane may run a DIFFERENT compression level than the
        # model lane (arXiv 2405.00965 observes the tracker tolerates
        # coarser quantization): None reuses the model compressor (and the
        # model gamma — bit-identical to the single-compressor wire)
        if isinstance(tracker_compressor, str):
            from repro.core.compression import make_compressor

            tracker_compressor = make_compressor(tracker_compressor)
        self.tracker_compressor = tracker_compressor

    @property
    def _tracker_comp(self) -> Compressor:
        return (self.tracker_compressor if self.tracker_compressor is not None
                else self.compressor)

    def _resolve_tracker_gamma(self, gamma: float, d: int) -> float:
        """Tracker-lane step size: an explicit ``tracker_gamma`` wins; else
        the model gamma when the lanes share a compressor (historical
        behavior, bit-identical), else the default resolution against the
        tracker compressor's own contraction factor."""
        if self.tracker_gamma_spec is not None:
            return float(self.tracker_gamma_spec)
        if self.tracker_compressor is None:
            return gamma
        comp = self.tracker_compressor
        delta = getattr(comp, "delta", 1.0)
        if hasattr(comp, "delta_for"):
            delta = comp.delta_for(max(int(d), 1))
        return 0.5 * max(delta, 1e-3)

    def init(self, theta_stacked):
        base = super().init(theta_stacked)
        if not self.tracker:
            return base
        tracker = choco_init(
            theta_stacked,
            cache_ops=self.union.n_ops if self.union is not None else 0,
            fault_ops=self.union.n_ops if self.faults is not None else None,
        )
        zeros = lambda: jax.tree.map(jnp.zeros_like, theta_stacked)
        return GTState(model=base, tracker=tracker, y=zeros(), d_prev=zeros())

    def mix(self, theta_half, state, key, ctx, *, step=None, mask=None,
            mixing=None, fault_key=None, theta_prev=None):
        if not self.tracker:
            return super().mix(
                theta_half, state, key, ctx, step=step, mask=mask,
                mixing=mixing, fault_key=fault_key,
            )
        if theta_prev is None:
            raise ValueError(
                "GradientTrackingConsensus.mix needs theta_prev (the round's "
                "pre-local-update theta) to form the local displacement — "
                "the trainer threads it; standalone callers must pass it"
            )
        d = self._encode_dim(theta_half)
        gamma = self._resolve_gamma(d)
        tgamma = self._resolve_tracker_gamma(gamma, d)
        f32 = jnp.float32

        def upd(h, p, y, dp):
            d = h.astype(f32) - p.astype(f32)
            if mask is not None:
                a = _gt_bcast(mask, h)
                y_half = y.astype(f32) + a * (d - dp.astype(f32))
                d_new = a * d + (1.0 - a) * dp.astype(f32)
                x_half = h.astype(f32) + a * (y_half - d)
            else:
                y_half = y.astype(f32) + d - dp.astype(f32)
                d_new = d
                x_half = p.astype(f32) + y_half
            return x_half.astype(h.dtype), y_half.astype(h.dtype), d_new.astype(h.dtype)

        trip = jax.tree.map(upd, theta_half, theta_prev, state.y, state.d_prev)
        x_half = jax.tree.map(lambda t: t[0], trip, is_leaf=lambda t: isinstance(t, tuple))
        y_half = jax.tree.map(lambda t: t[1], trip, is_leaf=lambda t: isinstance(t, tuple))
        d_prev_new = jax.tree.map(lambda t: t[2], trip, is_leaf=lambda t: isinstance(t, tuple))

        if (self.backend == "rolled" and self.faults is None
                and self.schedule is not None and mixing is None):
            mixing = self.schedule.mixing_at(0 if step is None else step, mask)
        (x_new, y_new), (model_new, tracker_new) = choco_round_lanes(
            (
                LaneRound(x_half, state.model, gamma, self.compressor),
                LaneRound(y_half, state.tracker, tgamma, self._tracker_comp),
            ),
            self.topology, key, packed=self.packed, fused=self.fused,
            mixing=mixing, mask=mask, backend=self.backend, mesh=self.mesh,
            node_axes=self.node_axes, schedule=self.schedule, step=step,
            union=self.union, faults=self.faults, fault_key=fault_key,
        )
        return x_new, GTState(
            model=model_new, tracker=tracker_new, y=y_new, d_prev=d_prev_new
        )

    @property
    def wire_format(self) -> wire.WireFormat:
        base = super().wire_format
        if not self.tracker:
            return base
        kind = base.lanes[0].kind
        tkind = kind
        if self.tracker_compressor is not None:
            tkind = (wire.DENSE.lanes[0].kind
                     if isinstance(self.tracker_compressor, Identity)
                     or not self.packed else kind)
        return wire.WireFormat(
            (wire.Lane(kind, "model"), wire.Lane(tkind, "tracker"))
        )

    def bits_per_round(self, theta_template, *, mode: str = "max",
                       step=None, mask=None, compressor=None) -> float:
        if compressor is not None:  # a single lane priced explicitly
            return super().bits_per_round(
                theta_template, mode=mode, step=step, mask=mask,
                compressor=compressor,
            )
        return sum(
            self.bits_per_lane(
                theta_template, mode=mode, step=step, mask=mask
            ).values()
        )

    def bits_per_lane(self, theta_template, *, mode: str = "max",
                      step=None, mask=None) -> dict:
        """Per-lane busiest-node bits, each lane priced at its OWN
        compressor (the tracker lane may be coarser, see
        ``tracker_compressor``)."""
        if not self.tracker:
            return super().bits_per_lane(
                theta_template, mode=mode, step=step, mask=mask
            )
        comps = {"model": self.compressor, "tracker": self._tracker_comp}
        return {
            lane.name: super(GradientTrackingConsensus, self).bits_per_round(
                theta_template, mode=mode, step=step, mask=mask,
                compressor=comps[lane.name],
            )
            for lane in self.wire_format
        }

    def bits_realized(self, theta_template, step, mask, consensus_state=None):
        if not self.tracker:
            return super().bits_realized(
                theta_template, step, mask, consensus_state=consensus_state
            )
        if self.faults is not None:
            meter = _fault_bits_meter(consensus_state)
            if meter is not None:
                return meter.max()
        scale = 2.0
        if self.tracker_compressor is not None:
            model_total = payload_total_bits(self.compressor, theta_template)
            scale = 1.0 + (
                payload_total_bits(self.tracker_compressor, theta_template)
                / model_total if model_total else 1.0
            )
        return scale * super().bits_realized(theta_template, step, mask)


class ExactConsensus(Consensus):
    """Uncompressed gossip: theta_i <- sum_j w_ij theta_j (DR-DSGD's wire).

    Accepts a :class:`TopologySchedule` too: the round then mixes with the
    schedule's dense W(t) and dropped nodes (identity row/column) hold their
    model until they rejoin.

    ``backend="ppermute"`` executes the mix on the neighbor-exchange
    substrate: dense-format f32 messages (this *is* the algorithm's wire —
    DR-DSGD sends uncompressed models) travel only between actual graph
    neighbors via ``lax.ppermute``, with zero all-gather; time variation
    rides the union wire's weight banks like the CHOCO consensus.
    """

    def __init__(self, topology: Topology | TopologySchedule, *,
                 backend: str = "rolled", mesh=None, node_axes="data",
                 faults=None):
        self.topology, self.schedule, _ = _split_schedule(topology)
        self.backend = backend
        self.mesh = mesh
        self.node_axes = node_axes
        self.faults = parse_fault_spec(faults)
        self.union = _resolve_wire_backend(
            backend, mesh, self.schedule, topology=self.topology, faults=self.faults
        )

    def init(self, theta_stacked):
        if self.faults is not None:
            # the uncompressed wire is memoryless (no mirrors to heal) —
            # the only fault state is the per-node delivered-bits meter
            m = jax.tree_util.tree_leaves(theta_stacked)[0].shape[0]
            return WireBits(bits=jnp.zeros((m,), jnp.float32))
        return ()

    def mix(self, theta_half, state, key, ctx, *, step=None, mask=None,
            mixing=None, fault_key=None, theta_prev=None):
        if self.backend == "ppermute":
            if mixing is not None:
                raise ValueError(
                    "backend='ppermute' takes step/mask, not a dense mixing "
                    "matrix — the wire program is compiled from the schedule"
                )
            from repro.core.exchange import mix_stacked_ppermute

            out = mix_stacked_ppermute(
                theta_half, self.topology, mesh=self.mesh,
                node_axes=self.node_axes, schedule=self.schedule,
                step=step, mask=mask, union=self.union,
                faults=self.faults, fault_key=fault_key,
            )
            if self.faults is not None:
                mixed, bits = out
                return mixed, WireBits(bits=bits)
            return out, state
        if self.faults is not None:
            from repro.core.exchange import mix_stacked_faulted_local

            mixed, bits = mix_stacked_faulted_local(
                theta_half, union=self.union, topology=self.topology,
                schedule=self.schedule, step=step, mask=mask,
                faults=self.faults, fault_key=fault_key,
            )
            return mixed, WireBits(bits=bits)
        if self.schedule is not None and mixing is None:
            mixing = self.schedule.mixing_at(0 if step is None else step, mask)
        if mixing is not None:
            return mix_stacked_with(theta_half, mixing), state
        return mix_stacked(theta_half, self.topology), state

    def bits_per_round(self, theta_template, *, mode: str = "max",
                       step=None, mask=None) -> float:
        if self.union is not None and self.faults is not None:
            # faulted wire: event draws are indexed per union op, so every
            # union op moves a dense f32 message every round — bill the
            # union degree, like the cached CHOCO wire does.
            return payload_bits(
                Identity(), theta_template, self.schedule,
                degree=_union_degree(self.union, self.schedule, mode, mask),
            )
        # fault-free scheduled ppermute now runs a per-phase wire program
        # (lax.switch over phase branches in mix_stacked_ppermute): only the
        # active phase's edges move bytes, so bill the schedule's own degree.
        return payload_bits(
            Identity(), theta_template, self.schedule or self.topology,
            mode=mode, step=step, mask=mask,
        )

    def bits_realized(self, theta_template, step, mask, consensus_state=None):
        if self.faults is not None:
            meter = _fault_bits_meter(consensus_state)
            if meter is not None:
                return meter.max()
        total = payload_total_bits(Identity(), theta_template)
        if self.union is not None and self.faults is not None:
            return total * self.union.realized_out_degree_traced(mask)
        topo = self.schedule or self.topology
        return total * topo.realized_degree_traced(step, mask)


class FedAvg(Consensus):
    """Federated server averaging over the sampled clients (DRFA's wire).

    Input is the stacked local models [m, ...]; output is the single server
    model (no node axis) — the trainer re-broadcasts it next round.  With no
    sampling ctx every client is averaged (plain FedAvg).

    ``backend="ppermute"`` aggregates mesh-native: per-device partial sums
    + one ``psum`` over the node axes (the ring all-reduce realization of
    "|U| models up, one model down") — zero all-gather, vs. the rolled form
    whose stacked ``sum(0)`` GSPMD may lower to an all-gather of the whole
    model stack.  ``bits_per_round`` keeps billing the server-star wire
    model (2|U|·d·f32) in every mode — that is the *algorithm's* traffic.
    """

    federated = True

    def __init__(self, num_sampled: int, *, backend: str = "rolled",
                 mesh=None, node_axes="data"):
        _resolve_wire_backend(backend, mesh, None)
        self.num_sampled = num_sampled
        self.backend = backend
        self.mesh = mesh
        self.node_axes = node_axes

    def mix(self, theta_locals, state, key, ctx, *, step=None, mask=None,
            mixing=None, fault_key=None, theta_prev=None):
        m = jax.tree_util.tree_leaves(theta_locals)[0].shape[0]
        sampled = ctx  # SampledAscent's per-round client mask (None = all)
        if sampled is None:
            sampled = jnp.ones((m,), jnp.float32)
        if self.backend == "ppermute":
            from repro.core.exchange import server_average_ppermute

            theta_new = server_average_ppermute(
                theta_locals, sampled, mesh=self.mesh, node_axes=self.node_axes
            )
            return theta_new, state
        wsum = sampled.sum()
        theta_new = jax.tree.map(
            lambda x: (
                (x.astype(jnp.float32) * sampled.reshape((m,) + (1,) * (x.ndim - 1))).sum(0)
                / wsum
            ).astype(x.dtype),
            theta_locals,
        )
        return theta_new, state

    def bits_per_round(self, theta_template, *, mode: str = "max",
                       step=None, mask=None) -> float:
        """Busiest node = the server: |U| models down + |U| models up, f32.
        The sample count is fixed, so every mode bills the same.
        ``theta_template`` is the federated trainer's *server* model (no
        node axis — federated state.theta never carries one), so the full
        prod(shape) is the per-model element count."""
        d = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(theta_template))
        return 2.0 * self.num_sampled * d * 32.0


# ==================================================================== trainer
class DecentralizedTrainer:
    """oracle x optimizer x dual x consensus, one round per ``step``.

    Functional interface shared by every algorithm in the repo::

        trainer = DecentralizedTrainer(loss_fn, num_nodes=m, local=..., dual=..., consensus=...)
        state = trainer.init(params, rng)
        state, aux = trainer.step(state, batch)     # jitted, donates state

    ``batch`` leaves are stacked [m, per-node-batch, ...].  See
    ``repro.core.adgda.adgda_trainer`` / ``repro.core.baselines`` for the
    paper's named compositions and ``examples/quickstart.py`` for an
    end-to-end run.
    """

    def __init__(
        self,
        loss_fn: LossFn,
        *,
        num_nodes: int,
        local: LocalUpdate,
        dual: DualUpdate,
        consensus: Consensus,
        prior: jax.Array | None = None,
        track_average: bool = True,
        config: Any = None,
    ):
        self.loss_fn = loss_fn
        self.num_nodes = num_nodes
        self.local = local
        self.dual = dual
        self.consensus = consensus
        self.prior = (
            jnp.full((num_nodes,), 1.0 / num_nodes) if prior is None else jnp.asarray(prior)
        )
        self.track_average = track_average
        self.config = config  # the factory's config, kept for introspection
        self.federated = consensus.federated

    def _init_as(self, composed: "DecentralizedTrainer") -> None:
        """Deprecated-shim helper: adopt a factory-built trainer's composition
        wholesale, so the shims cannot drift from the factories field-by-field."""
        DecentralizedTrainer.__init__(
            self,
            composed.loss_fn,
            num_nodes=composed.num_nodes,
            local=composed.local,
            dual=composed.dual,
            consensus=composed.consensus,
            prior=composed.prior,
            track_average=composed.track_average,
            config=composed.config,
        )

    # convenience introspection (shim/test surface)
    @property
    def topology(self) -> Topology | None:
        return getattr(self.consensus, "topology", None)

    @property
    def schedule(self) -> TopologySchedule | None:
        """The time-varying topology schedule, or None when the wire is static."""
        return getattr(self.consensus, "schedule", None)

    @property
    def compressor(self) -> Compressor | None:
        return getattr(self.consensus, "compressor", None)

    @property
    def gamma(self) -> float | None:
        return getattr(self.consensus, "gamma", None)

    def _stacked(self, params):
        m = self.num_nodes
        return jax.tree.map(lambda p: jnp.broadcast_to(p[None], (m,) + p.shape), params)

    # ------------------------------------------------------------------ init
    def init(self, params: Any, rng: jax.Array) -> TrainerState:
        stacked = self._stacked(params)
        if self.federated:
            theta0 = jax.tree.map(lambda x: jnp.array(x, copy=True), params)
        else:
            theta0 = jax.tree.map(lambda x: x.copy(), stacked)
        return TrainerState(
            step=jnp.zeros((), jnp.int32),
            theta=theta0,
            lam=self.dual.init(self.num_nodes),
            opt=self.local.init(stacked),
            consensus=self.consensus.init(stacked),
            theta_avg=(
                jax.tree.map(lambda p: jnp.array(p, jnp.float32, copy=True), params)
                if self.track_average
                else ()
            ),
            # defensive copy: step() donates its input state, which would
            # otherwise delete the caller's key buffer
            rng=jnp.array(rng, copy=True),
        )

    # ------------------------------------------------------------------ step
    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def step(self, state: TrainerState, batch: Any) -> tuple[TrainerState, dict]:
        return self.step_impl(state, batch)

    def step_impl(self, state: TrainerState, batch: Any) -> tuple[TrainerState, dict]:
        """Unjitted round — lower/compile with custom shardings via
        ``jax.jit(trainer.step_impl, in_shardings=...)`` (see launch/dryrun.py)."""
        m = self.num_nodes
        schedule = self.schedule
        needs_mask = schedule is not None and schedule.dropout_rate > 0

        # --- RNG: one split per round; extra keys only for the parts that
        # consume randomness, so compositions without them (e.g. DR-DSGD)
        # reproduce the seed trainers' key streams exactly — and a static
        # no-dropout run reproduces the pre-schedule stream exactly
        needs_faults = getattr(self.consensus, "faults", None) is not None
        n_extra = (
            int(self.consensus.needs_key) + int(self.dual.needs_key)
            + int(needs_mask) + int(needs_faults)
        )
        keys = jax.random.split(state.rng, m + 1 + n_extra)
        rng, idx = keys[0], 1
        gossip_key = None
        if self.consensus.needs_key:
            gossip_key, idx = keys[idx], idx + 1
        dual_key = None
        if self.dual.needs_key:
            dual_key, idx = keys[idx], idx + 1
        mask_key = None
        if needs_mask:
            mask_key, idx = keys[idx], idx + 1
        fault_key = None
        if needs_faults:
            # one event key per round, shared by the model gossip and the
            # lambda gossip: the dual rides the same physical messages, so
            # both see the same delivery-fault draw
            fault_key, idx = keys[idx], idx + 1
        node_keys = keys[idx:]

        # --- time-varying wire: participation mask + this round's W(t) ------
        # the dense [m, m] matrix only exists for the rolled backend; the
        # ppermute backend compiles its own union wire program and the dual
        # gossip rides it through mix_fn (wire_mix) instead.  Faulted wires
        # also skip it: per-edge delivery faults have no dense-W expression,
        # so every faulted backend runs the union exchange.
        wire_native = getattr(self.consensus, "backend", "rolled") == "ppermute"
        mask = schedule.mask_at(mask_key, state.step) if needs_mask else None
        mixing = (
            schedule.mixing_at(state.step, mask)
            if schedule is not None and not wire_native and not needs_faults
            else None
        )

        # Each phase of Algorithm 1 runs under a named scope: the compiled
        # ops carry it in their op_name, so a profiler trace of the round
        # splits its device time by phase.  Scopes are metadata only.
        with jax.named_scope("adgda.dual"):
            ctx = self.dual.begin(state.lam, dual_key)

        # --- local oracle + optimizer (dual-weighted gradients) -------------
        theta = self._stacked(state.theta) if self.federated else state.theta
        with jax.named_scope("adgda.local"):
            weights_fn = lambda losses: self.dual.grad_weights(state.lam, losses)
            theta_half, opt_new, losses = self.local.step(
                self.loss_fn, theta, state.opt, batch, node_keys, weights_fn
            )
            if mask is not None:
                # dropped nodes skip their local update: model and per-node
                # optimizer moments revert, so a rejoining node resumes from
                # exactly where it left off
                theta_half = _select_nodes(mask, theta_half, theta, m)
                opt_new = _select_nodes(mask, opt_new, state.opt, m)

        # --- dual update ----------------------------------------------------
        with jax.named_scope("adgda.dual"):
            lam_new = self.dual.update(
                state.lam, losses, ctx, mixing=mixing, mask=mask, step=state.step,
                fault_key=fault_key,
            )

        # --- consensus ------------------------------------------------------
        with jax.named_scope("adgda.gossip"):
            theta_new, cons_new = self.consensus.mix(
                theta_half, state.consensus, gossip_key, ctx,
                step=state.step, mask=mask, mixing=mixing, fault_key=fault_key,
                theta_prev=theta,
            )

        with jax.named_scope("adgda.telemetry"):
            # --- running average of the network mean (output theta_o) -------
            if self.track_average:
                tt = state.step.astype(jnp.float32)
                mean = (lambda th: th.astype(jnp.float32)) if self.federated else (
                    lambda th: th.astype(jnp.float32).mean(0)
                )
                theta_avg = jax.tree.map(
                    lambda avg, th: (avg * tt + mean(th)) / (tt + 1.0),
                    state.theta_avg,
                    theta_new,
                )
            else:
                theta_avg = ()

            aux = {
                "losses": losses,
                "worst_loss": losses.max(),
                "mean_loss": losses.mean(),
                "lambda_mean": lam_new.mean(0) if lam_new.ndim == 2 else lam_new,
            }
            if not self.federated:
                aux["consensus_err"] = _consensus_error(theta_new)
            if mask is not None:
                aux["participation"] = mask
            # jitted realized-bits meter: this round's measured wire traffic
            # (model payload + the dual's constant), no host-side masks needed;
            # faulted wires read the exchange's own delivered-bits meter out of
            # the post-mix consensus state instead of a degree formula
            aux["bits_realized"] = self.consensus.bits_realized(
                state.theta, state.step, mask, consensus_state=cons_new
            ) + jnp.float32(self.dual.bits_per_round())

        new_state = TrainerState(
            step=state.step + 1,
            theta=theta_new,
            lam=lam_new,
            opt=opt_new,
            consensus=cons_new,
            theta_avg=theta_avg,
            rng=rng,
        )
        return new_state, aux

    # ------------------------------------------------------------- utilities
    def network_mean(self, state: TrainerState):
        if self.federated:
            return jax.tree.map(lambda x: x.astype(jnp.float32), state.theta)
        return jax.tree.map(lambda x: x.astype(jnp.float32).mean(0), state.theta)

    def bits_per_round(self, state: TrainerState, per_iteration: bool = False,
                       *, mode: str = "max", step=None, mask=None) -> float:
        """Bits transmitted per communication round by the busiest node
        (model payload + dual traffic).

        One round covers ``local_steps`` gradient iterations;
        ``per_iteration=True`` divides by that, putting algorithms with
        different communication intervals (DRFA, AD-GDA-K) on equal footing.

        ``mode`` controls the dropout accounting of the model payload:
        ``"max"`` (default) bills the busiest-phase max degree — the upper
        bound provisioning must budget for; ``"expected"`` bills the
        participation-aware expected active degree (phase-averaged, times
        the (1-rate)^2 link-survival probability); ``"realized"`` bills
        round ``step``'s actual links under the concrete participation
        ``mask`` (e.g. ``aux["participation"]``).  The dual's m-float
        traffic stays at its upper bound in every mode — it is negligible
        next to the model payload and not worth a mask-aware estimate.

        With a fault model active, ``mode="realized"`` reads the exchange's
        in-graph delivered-bits meter out of ``state.consensus`` (last
        round's actual deliveries: drops unbilled, dups twice, resyncs
        dense) instead of a degree formula.
        """
        if mode == "realized" and getattr(self.consensus, "faults", None) is not None:
            meter = _fault_bits_meter(state.consensus)
            if meter is not None:
                bits = float(jnp.max(meter)) + self.dual.bits_per_round()
                if per_iteration:
                    bits /= self.local.local_steps
                return bits
        bits = (
            self.consensus.bits_per_round(state.theta, mode=mode, step=step, mask=mask)
            + self.dual.bits_per_round()
        )
        if per_iteration:
            bits /= self.local.local_steps
        return bits


def _consensus_error(theta_stacked) -> jax.Array:
    """Xi_theta = sum_i ||theta_i - theta_bar||^2 over all leaves."""
    err = 0.0
    for leaf in jax.tree_util.tree_leaves(theta_stacked):
        leaf = leaf.astype(jnp.float32)
        mean = leaf.mean(0, keepdims=True)
        err = err + jnp.sum((leaf - mean) ** 2)
    return err
