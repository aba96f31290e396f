"""End-to-end AD-GDA training driver.

Runs the paper's Algorithm 1 on any assigned architecture with the synthetic
heterogeneous LM pipeline.  On real hardware pass ``--mesh prod`` /
``--mesh multipod``; on this CPU container use the default local mesh with a
reduced config (``--reduced``), which is what ``examples/train_transformer.py``
demonstrates.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
      --steps 100 --nodes 4 --compressor q4b --topology ring

Fault-tolerant / time-varying runs gossip over a topology schedule with
optional per-round Bernoulli node dropout, and long runs are survivable:
``--checkpoint ckpt/run --checkpoint-every 50`` persists the **entire**
trainer state (theta, lambda, optimizer moments, CHOCO trackers, rng, step)
and ``--resume`` picks up from the latest checkpoint bit-identically to an
uninterrupted run (the synthetic data stream is deterministic and is
fast-forwarded to the resume step):

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
      --steps 200 --topology-schedule roundrobin:ring,torus --dropout 0.2 \
      --checkpoint ckpt/run --checkpoint-every 50 --resume
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_latest, save
from repro.configs import get_config
from repro.data import node_token_stream
from repro.launch import steps as st
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_node_mesh
from repro.launch.sharding import node_shardings


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="2-layer smoke-scale variant (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--batch-per-node", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--topology-schedule", default=None,
                    help="time-varying wire: 'roundrobin:ring,torus', "
                         "'matching[:P]', or a static topology name")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-round Bernoulli node-dropout probability")
    ap.add_argument("--topology-p", type=float, default=None,
                    help="edge probability for --topology erdos_renyi")
    ap.add_argument("--topology-seed", type=int, default=0,
                    help="graph-sampling seed (erdos_renyi, matching schedules)")
    ap.add_argument("--fault-spec", default=None,
                    help="wire-fault injection, e.g. 'drop:0.05,corrupt:0.01,"
                         "stale:2' — per-(edge,round) message drop/corrupt/"
                         "dup/delay with digest detection and staleness-"
                         "bounded self-healing resync (repro.core.faults)")
    ap.add_argument("--compressor", default="q4b")
    ap.add_argument("--alpha", type=float, default=0.01)
    ap.add_argument("--eta-theta", type=float, default=0.05)
    ap.add_argument("--eta-lambda", type=float, default=0.01)
    ap.add_argument("--optimizer", choices=("sgd", "adam"), default="sgd",
                    help="primal update rule (repro.optim)")
    ap.add_argument("--schedule", choices=("const", "exp", "cosine"), default="exp",
                    help="LR schedule; exp decays by --lr-decay per round")
    ap.add_argument("--lr-decay", type=float, default=1.0,
                    help="per-round decay factor for --schedule exp")
    ap.add_argument("--warmup", type=int, default=0, help="linear LR warmup rounds")
    ap.add_argument("--momentum", type=float, default=0.0, help="SGD momentum")
    ap.add_argument("--nesterov", action="store_true", help="Nesterov momentum (sgd)")
    ap.add_argument("--local-steps", type=int, default=1,
                    help="K local optimizer steps between gossip rounds (needs K x batch)")
    ap.add_argument("--consensus", choices=("choco", "gt"), default="choco",
                    help="'choco' = plain compressed gossip; 'gt' = gradient "
                         "tracking: a second CHOCO-compressed tracker variable "
                         "rides lane 2 of the same wire round, cancelling the "
                         "client drift large --local-steps induce under "
                         "heterogeneous data (2x per-round bits)")
    ap.add_argument("--tracker-compressor", default=None,
                    help="compression level for the gt tracker lane only "
                         "(e.g. kq2b beside a kq4b model lane); default "
                         "reuses --compressor on both lanes")
    ap.add_argument("--tracker-gamma", type=float, default=None,
                    help="consensus step size for the gt tracker lane "
                         "(default: same resolution as the model lane)")
    ap.add_argument("--fused-gossip", action="store_true",
                    help="single-pass Pallas gossip (requires a kq* compressor)")
    ap.add_argument("--gossip-backend", choices=("rolled", "ppermute"), default="rolled",
                    help="wire model: 'rolled' simulates the network on the "
                         "stacked array (reference oracle); 'ppermute' runs "
                         "the gossip under shard_map, exchanging only packed "
                         "compressed payloads between graph neighbors via "
                         "collective-permute (multi-device: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N on CPU)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default=None, help="path prefix for npz checkpoints")
    ap.add_argument("--checkpoint-every", type=int, default=100,
                    help="save the full trainer state every N completed rounds")
    ap.add_argument("--resume", action="store_true",
                    help="restore the full trainer state from the latest "
                         "--checkpoint file and continue (bit-identical to an "
                         "uninterrupted run)")
    ap.add_argument("--metrics-out", default=None,
                    help="write final losses/consensus_err to this JSON file")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    seq = args.seq
    if cfg.ssm_state:
        seq = max(seq, cfg.ssm_chunk)
        seq -= seq % cfg.ssm_chunk

    mesh = None
    if args.gossip_backend == "ppermute":
        # place the node shards: the data axis carries contiguous node blocks
        # and the SPMD gossip's collective-permutes run between its devices
        mesh = make_node_mesh(args.nodes)
        print(f"gossip backend=ppermute over mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
              f"({args.nodes // mesh.devices.shape[0]} node(s)/device)")

    trainer = st.make_trainer(
        cfg,
        args.nodes,
        topology=args.topology,
        topology_schedule=args.topology_schedule,
        dropout=args.dropout,
        topology_p=args.topology_p,
        topology_seed=args.topology_seed,
        fault_spec=args.fault_spec,
        compressor=args.compressor,
        alpha=args.alpha,
        eta_theta=args.eta_theta,
        eta_lambda=args.eta_lambda,
        optimizer=args.optimizer,
        schedule=args.schedule,
        lr_decay=args.lr_decay,
        warmup=args.warmup,
        total_steps=args.steps,
        momentum=args.momentum,
        nesterov=args.nesterov,
        local_steps=args.local_steps,
        consensus=args.consensus,
        tracker_gamma=args.tracker_gamma,
        tracker_compressor=args.tracker_compressor,
        fused_gossip=args.fused_gossip,
        gossip_backend=args.gossip_backend,
        mesh=mesh,
        track_average=False,
    )

    key = jax.random.PRNGKey(args.seed)
    n_params = sum(
        int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(st.abstract_params(cfg))
    )
    wire = args.topology_schedule or args.topology
    if args.dropout:
        wire += f"+drop{args.dropout:g}"
    if args.fault_spec:
        wire += f"+faults[{args.fault_spec}]"
    if args.consensus == "gt":
        wire += f"+gt[{trainer.consensus.wire_format}]"
    print(f"arch={cfg.name} params={n_params:,} nodes={args.nodes} "
          f"compressor={args.compressor} topology={wire}")

    init_rng = jax.random.PRNGKey(args.seed + 1)
    start_step = 0
    if args.resume:
        if not args.checkpoint:
            raise SystemExit("--resume requires --checkpoint")
        # restore the *entire* trainer state into the abstract template — no
        # recompute, and the continuation is bit-identical to a run that
        # never stopped.  restore_latest skips any unreadable file and falls
        # back to the last complete checkpoint instead of crashing.
        template = st.abstract_trainer_state(trainer, cfg)
        state, found = restore_latest(args.checkpoint, template)
        if found is None:
            print(f"--resume: no loadable checkpoint under {args.checkpoint!r}; starting fresh")
            state = st.init_trainer_state(trainer, cfg, key, init_rng, mesh)
        else:
            start_step = found
            print(f"resumed full trainer state from step {found}")
            if mesh is not None:
                state = jax.device_put(
                    state, node_shardings(state, mesh, args.nodes)
                )
    else:
        # built straight into its node shards: no device ever holds the
        # whole stacked state
        state = st.init_trainer_state(trainer, cfg, key, init_rng, mesh)

    # one round consumes local_steps x the per-node batch (K local updates)
    round_batch = args.batch_per_node * args.local_steps
    stream = node_token_stream(args.nodes, round_batch, seq, cfg.vocab_size, seed=args.seed)
    for _ in range(start_step):  # deterministic stream: fast-forward to resume point
        next(stream)

    def make_batch(tokens):
        batch = {"tokens": tokens}
        if cfg.is_encdec:
            batch["frames"] = jnp.zeros(
                (args.nodes, round_batch, cfg.encoder_context, cfg.d_model), jnp.float32
            )
        if cfg.num_patches > 0:
            batch["patches"] = jnp.zeros(
                (args.nodes, round_batch, cfg.num_patches, cfg.d_model), jnp.float32
            )
        if mesh is not None:
            return jax.device_put(batch, node_shardings(batch, mesh, args.nodes))
        return jax.tree.map(jnp.asarray, batch)

    aux = None
    t0 = time.time()
    for step in range(start_step, args.steps):
        state, aux = trainer.step(state, make_batch(next(stream)))
        if step % args.log_every == 0 or step == args.steps - 1:
            losses = np.asarray(aux["losses"])
            alive = (
                f"alive={int(np.asarray(aux['participation']).sum())}/{args.nodes}  "
                if "participation" in aux else ""
            )
            print(
                f"step {step:5d}  worst={losses.max():.4f}  mean={losses.mean():.4f}  "
                f"consensus={float(aux['consensus_err']):.3e}  {alive}"
                f"lambda_max={float(aux['lambda_mean'].max()):.3f}  "
                f"bits/round={trainer.bits_per_round(state):.3e}  "
                f"({(time.time() - t0) / (step - start_step + 1):.2f}s/step)"
            )
        done = step + 1
        if args.checkpoint and done % args.checkpoint_every == 0 and done < args.steps:
            fname = save(args.checkpoint, state, step=done)
            print(f"checkpointed full trainer state to {fname}")

    if args.checkpoint:
        fname = save(args.checkpoint, state, step=args.steps)
        base = args.checkpoint[:-4] if args.checkpoint.endswith(".npz") else args.checkpoint
        model_file = save(base + "_model", trainer.network_mean(state))
        print(f"saved final state to {fname}, consensus model to {model_file}")

    if args.metrics_out and aux is not None:
        metrics = {
            "final_step": args.steps,
            "losses": [float(x) for x in np.asarray(aux["losses"])],
            "worst_loss": float(np.asarray(aux["losses"]).max()),
            "consensus_err": float(aux["consensus_err"]),
        }
        with open(args.metrics_out, "w") as f:
            json.dump(metrics, f, indent=2)
        print(f"wrote metrics to {args.metrics_out}")


if __name__ == "__main__":
    main()
