"""Serving driver: batched autoregressive decode on the consensus model.

Demonstrates the decode path every assigned arch implements (KV ring
buffers, SSM/RG-LRU O(1) state).  CPU-scale by default (--reduced).

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --reduced \
      --batch 4 --prompt-len 64 --gen 32

``--fleet N`` switches to the decentralized serving fleet: N nodes of
continuous-batching engines behind bounded-queue admission control, fed by
the seeded Poisson/Zipf load generator, reporting the suite-S latency/SLO
vocabulary (p50/p95/p99 TTFT in ticks and ms, tokens/s, queue depth, slot
occupancy).  With ``--follow`` the fleet polls ``--restore`` (a step-tagged
checkpoint prefix, the spelling launch/train.py --checkpoint writes) and
hot-reloads new consensus weights while serving — the train-and-serve loop:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --fleet 2 --rate 0.2 --requests 64 --follow --restore /tmp/run/consensus \
      --metrics-out serve_metrics.json

``--metrics-out`` writes the final metrics JSON (same flag vocabulary as
launch/train.py).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore, step_path
from repro.configs import get_config
from repro.launch import steps as st
from repro.launch.compile_cache import configure_compile_cache
from repro.models import transformer as T


def _resolve_restore(path: str) -> str:
    """Accept the path spellings checkpoint.latest_step does: an exact file,
    a missing-.npz suffix, or a step-tagged prefix."""
    if os.path.exists(path):
        return path
    if os.path.exists(path + ".npz"):
        return path + ".npz"
    found = latest_step(path)
    if found is None:
        raise SystemExit(
            f"--restore: no checkpoint at {path!r} (tried the exact path, "
            "with a .npz suffix, and as a step-tagged prefix)"
        )
    return step_path(path, found)


def _run_fleet(args, cfg, params) -> None:
    """The decentralized serving fleet: N nodes, admission control, seeded
    Poisson/Zipf traffic, optional --follow hot reload from --restore."""
    from repro.serving import (
        AdmissionControl,
        FleetNode,
        HotReloader,
        LoadGenConfig,
        LoadGenerator,
        ServeEngine,
        ServingFleet,
    )

    bucket = 8
    prompt_max = max(args.prompt_len, 4)
    padded = -(-prompt_max // bucket) * bucket
    cache_len = args.cache_len or (padded + args.gen)
    gen = LoadGenerator(LoadGenConfig(
        num_nodes=args.fleet, rate=args.rate, vocab_size=cfg.vocab_size,
        prompt_min=4, prompt_max=prompt_max,
        output_min=1, output_max=args.gen, seed=args.seed,
        prompt_mode={"iid": "iid", "zipf": "pool", "unique": "unique"}[args.prompts],
        prompt_pool=args.prompt_pool,
    ))
    nodes = [
        FleetNode(
            i,
            ServeEngine(cfg, params, max_slots=args.slots, cache_len=cache_len,
                        prompt_bucket=bucket, fastpath=not args.no_fastpath,
                        prefix_cache=args.prefix_cache),
            admission=AdmissionControl(max_queue=args.max_queue,
                                       policy=args.admission),
            reloader=(HotReloader(args.restore, params) if args.follow else None),
        )
        for i in range(args.fleet)
    ]
    if args.follow:
        # start from the newest complete checkpoint already on disk
        for node in nodes:
            node.maybe_reload()
    fleet = ServingFleet(nodes, gen,
                         reload_every=args.reload_every if args.follow else 0)
    rep = fleet.run(max_requests=args.requests, max_ticks=1_000_000)

    f = rep.fleet
    reloads = sum(n.reloader.reloads for n in nodes if n.reloader)
    print(f"fleet={args.fleet}x{args.slots} rate={args.rate}/node "
          f"offered={rep.offered} completed={f['completed']} "
          f"rejected={f['rejected']} shed={f['shed']} ticks={rep.ticks}")
    print(f"ttft ticks p50/p95/p99 = {f['p50_ttft_ticks']:.0f}/"
          f"{f['p95_ttft_ticks']:.0f}/{f['p99_ttft_ticks']:.0f}  "
          f"ttft ms p50/p99 = {f['p50_ttft_ms']:.1f}/{f['p99_ttft_ms']:.1f}  "
          f"{f['tok_per_s']:.1f} tok/s  {f['per_token_ms']:.1f} ms/token")
    print(f"queue depth mean/max = {f['mean_queue_depth']:.2f}/"
          f"{f['max_queue_depth']:.0f}  slot occupancy = {f['slot_occupancy']:.2f}"
          + (f"  reloads = {reloads}" if args.follow else ""))
    print(f"cache_hit_rate = {f['cache_hit_rate']:.3f}  "
          f"prefill_skipped = {f['prefill_skipped']:.0f}")
    if args.metrics_out:
        payload = {
            "arch": cfg.name,
            "fleet": args.fleet,
            "slots": args.slots,
            "rate": args.rate,
            "offered": rep.offered,
            "ticks": rep.ticks,
            "wall_seconds": rep.wall_seconds,
            "metrics": f,
            "nodes": rep.node_summaries,
        }
        if args.follow:
            payload["reloads"] = reloads
            payload["reload_steps"] = [n.reloader.step for n in nodes]
        with open(args.metrics_out, "w") as fh:
            json.dump(payload, fh, indent=2, default=float)
        print(f"metrics -> {args.metrics_out}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=None)
    ap.add_argument("--restore", default=None,
                    help="model checkpoint to load: an exact .npz file, the "
                         "same path without the .npz suffix, or a step-tagged "
                         "prefix (resolves to the latest <prefix>_<step>.npz, "
                         "the spelling launch/train.py --checkpoint writes)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None,
                    help="write final serving metrics to this JSON file "
                         "(same flag as launch/train.py)")
    fleet = ap.add_argument_group("fleet mode (decentralized serving)")
    fleet.add_argument("--fleet", type=int, default=0, metavar="N",
                       help="serve as a fleet of N nodes (continuous batching "
                            "+ admission control + seeded load generator) "
                            "instead of one fixed batch")
    fleet.add_argument("--rate", type=float, default=0.2,
                       help="offered load per node, requests/engine-tick")
    fleet.add_argument("--requests", type=int, default=64,
                       help="total requests to offer across the fleet")
    fleet.add_argument("--slots", type=int, default=2,
                       help="continuous-batching slots per node")
    fleet.add_argument("--max-queue", type=int, default=12,
                       help="bounded pending-queue length per node")
    fleet.add_argument("--admission", choices=("reject", "shed_oldest"),
                       default="reject", help="overload policy")
    fleet.add_argument("--follow", action="store_true",
                       help="poll --restore (a step-tagged prefix) while "
                            "serving and hot-reload each new complete "
                            "checkpoint (train-and-serve)")
    fleet.add_argument("--reload-every", type=int, default=16,
                       help="poll cadence in engine ticks for --follow")
    fleet.add_argument("--prompts", choices=("iid", "zipf", "unique"),
                       default="iid",
                       help="prompt repetition structure: iid (historical "
                            "stream), zipf (hot pool of --prompt-pool prompts "
                            "-- the prefix-cache workload), unique (provably "
                            "distinct prompts, zero-hit-rate control)")
    fleet.add_argument("--prompt-pool", type=int, default=64,
                       help="pool size for --prompts zipf")
    fleet.add_argument("--prefix-cache", type=int, default=64,
                       help="prefix KV cache entries per engine (0 disables)")
    fleet.add_argument("--no-fastpath", action="store_true",
                       help="serve with the legacy engine (no prefix cache, "
                            "batch-1 prefill, full-pool decode) -- tick "
                            "metrics are bit-identical, only wall differs")
    args = ap.parse_args()
    configure_compile_cache()

    if args.follow and not (args.fleet and args.restore):
        ap.error("--follow needs --fleet N and --restore <step-tagged prefix>")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    S = args.prompt_len
    if cfg.ssm_state:
        S = max(S, cfg.ssm_chunk)
        S -= S % cfg.ssm_chunk
    cache_len = args.cache_len or (S + args.gen)

    key = jax.random.PRNGKey(args.seed)
    params = T.init_model(key, cfg)
    if args.restore and not args.follow:
        fname = _resolve_restore(args.restore)
        try:
            params = restore(fname, params)
        except KeyError as e:
            raise SystemExit(
                f"--restore: {fname} does not hold a bare model parameter tree "
                f"({e}); full trainer-state checkpoints from launch/train.py "
                "serve via their companion '<prefix>_model.npz' consensus file"
            ) from None
        print(f"restored params from {fname}")

    if args.fleet:
        _run_fleet(args, cfg, params)
        return

    batch = {"tokens": jax.random.randint(key, (args.batch, S), 0, cfg.vocab_size)}
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            key, (args.batch, cfg.encoder_context, cfg.d_model), jnp.float32
        ) * 0.02
    if cfg.num_patches > 0:
        batch["patches"] = jax.random.normal(
            key, (args.batch, cfg.num_patches, cfg.d_model), jnp.float32
        ) * 0.02

    prefill = jax.jit(st.make_prefill_step(cfg, cache_len=cache_len))
    decode = jax.jit(st.make_decode_step(cfg))

    t0 = time.time()
    logits, cache = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits[:, -1:, :], axis=-1)
    out = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = decode(params, cache, tok, jnp.int32(S + i))
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits[:, -1, :] / args.temperature)[:, None]
        else:
            tok = jnp.argmax(logits[:, -1:, :], axis=-1)
        out.append(tok)
    jax.block_until_ready(out[-1])
    t_decode = time.time() - t0

    tokens = np.asarray(jnp.concatenate(out, axis=1))
    per_token_ms = t_decode / max(args.gen - 1, 1) * 1e3
    print(f"arch={cfg.name} prefill({args.batch}x{S})={t_prefill:.2f}s "
          f"decode {args.gen - 1} steps={t_decode:.2f}s "
          f"({per_token_ms:.1f} ms/token)")
    print("generated token ids (first row):", tokens[0][:24].tolist())
    assert np.isfinite(np.asarray(logits, np.float32)).all(), "NaN in decode logits"
    if args.metrics_out:
        with open(args.metrics_out, "w") as fh:
            json.dump({
                "arch": cfg.name,
                "batch": args.batch,
                "prompt_len": S,
                "gen": args.gen,
                "prefill_seconds": t_prefill,
                "decode_seconds": t_decode,
                "per_token_ms": per_token_ms,
            }, fh, indent=2)
        print(f"metrics -> {args.metrics_out}")


if __name__ == "__main__":
    main()
