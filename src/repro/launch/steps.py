"""Step-function builders: the glue between the model zoo and the trainers.

``make_trainer(cfg, num_nodes, ...)`` wires an architecture's ``lm_loss``
into a composed AD-GDA :class:`~repro.core.trainer.DecentralizedTrainer`
(paper Algorithm 1) — optimizer, schedule and gossip dispatch are all
selectable here, which is what the ``repro.launch.train`` CLI exposes.
``make_prefill_step`` / ``make_decode_step`` build the serving entry points
on the *consensus* model (no node axis).
"""
from __future__ import annotations

import jax

from repro.core.adgda import ADGDAConfig, adgda_trainer
from repro.core.trainer import DecentralizedTrainer
from repro.launch.sharding import node_shardings
from repro.models import transformer as T
from repro.models.config import ModelConfig

__all__ = [
    "make_trainer",
    "init_trainer_state",
    "make_prefill_step",
    "make_decode_step",
    "abstract_params",
    "abstract_trainer_state",
]


def make_trainer(
    cfg: ModelConfig,
    num_nodes: int,
    *,
    topology: str = "ring",
    topology_schedule: str | None = None,
    dropout: float = 0.0,
    topology_p: float | None = None,
    topology_seed: int = 0,
    fault_spec: str | None = None,
    compressor: str = "q4b",
    alpha: float = 0.01,
    eta_theta: float = 0.1,
    eta_lambda: float = 0.01,
    track_average: bool = False,
    packed_gossip: bool = True,
    fused_gossip: bool = False,
    gossip_backend: str = "rolled",
    mesh=None,
    node_axes="data",
    robust: bool = True,
    microbatches: int = 1,
    grad_accum_dtype: str = "float32",
    local_steps: int = 1,
    consensus: str = "choco",
    tracker_gamma: float | None = None,
    tracker_compressor: str | None = None,
    optimizer: str = "sgd",
    schedule: str = "exp",
    lr_decay: float = 1.0,
    warmup: int = 0,
    total_steps: int = 1000,
    momentum: float = 0.0,
    nesterov: bool = False,
    spmd_axis_name=None,
) -> DecentralizedTrainer:
    def loss_fn(params, batch, rng):
        return T.lm_loss(params, batch, cfg, rng)

    adgda_cfg = ADGDAConfig(
        num_nodes=num_nodes,
        topology=topology,
        topology_schedule=topology_schedule,
        dropout=dropout,
        topology_p=topology_p,
        topology_seed=topology_seed,
        fault_spec=fault_spec,
        compressor=compressor,
        alpha=alpha,
        eta_theta=eta_theta,
        eta_lambda=eta_lambda,
        track_average=track_average,
        packed_gossip=packed_gossip,
        fused_gossip=fused_gossip,
        gossip_backend=gossip_backend,
        robust=robust,
        microbatches=microbatches,
        grad_accum_dtype=grad_accum_dtype,
        local_steps=local_steps,
        consensus=consensus,
        tracker_gamma=tracker_gamma,
        tracker_compressor=tracker_compressor,
        optimizer=optimizer,
        schedule=schedule,
        lr_decay=lr_decay,
        warmup=warmup,
        total_steps=total_steps,
        momentum=momentum,
        nesterov=nesterov,
        spmd_axis_name=spmd_axis_name,
    )
    return adgda_trainer(adgda_cfg, loss_fn, mesh=mesh, node_axes=node_axes)


def init_trainer_state(trainer: DecentralizedTrainer, cfg: ModelConfig,
                       key: jax.Array, rng: jax.Array, mesh=None):
    """Random model parameters and the trainer state built on them, in one
    jitted program whose outputs land where the step reads them.

    With ``mesh`` every stacked ``[num_nodes, ...]`` leaf is placed node-
    sharded over its ``data`` axis (``sharding.node_shardings``), so no
    device ever holds more than its own nodes; without one the state lands on
    the default device. No host copy and no unstacked parameter tree outlive
    the program."""

    def init(k, r):
        return trainer.init(T.init_model(k, cfg), r)

    if mesh is None:
        return jax.jit(init)(key, rng)
    shardings = node_shardings(
        jax.eval_shape(init, key, rng), mesh, trainer.num_nodes
    )
    return jax.jit(init, out_shardings=shardings)(key, rng)


def make_prefill_step(cfg: ModelConfig, cache_len: int):
    def prefill_step(params, batch):
        return T.prefill(params, batch, cfg, cache_len)

    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, cache, tokens, pos):
        return T.decode_step(params, tokens, cache, pos, cfg)

    return decode_step


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct pytree of the model parameters (no allocation)."""
    return jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.PRNGKey(0))


def abstract_cache(cfg: ModelConfig, batch: int, length: int):
    return jax.eval_shape(lambda: T.init_cache(cfg, batch, length))


def abstract_trainer_state(trainer: DecentralizedTrainer, cfg: ModelConfig):
    params = abstract_params(cfg)
    return jax.eval_shape(trainer.init, params, jax.random.PRNGKey(0))


# deprecated alias (pre-refactor name)
abstract_adgda_state = abstract_trainer_state
