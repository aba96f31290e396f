import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.
"""Multi-pod dry-run: prove every (arch x input-shape x mesh) combination
lowers, compiles, and fits — without real hardware.

For each pair this script builds the production step function:

  train_4k     -> AD-GDA Algorithm-1 step (lambda-weighted loss, compressed
                  ring gossip, dual averaging) over m = 16 (single-pod) or
                  32 (multi-pod) nodes,
  prefill_32k  -> full forward + cache priming on the consensus model,
  decode_32k / long_500k -> one-token serve step against a seq_len cache,

then ``jax.jit(step, in_shardings=...).lower(*abstract).compile()`` on the
(16, 16) = 256-chip and (2, 16, 16) = 512-chip meshes, prints
``memory_analysis()`` / ``cost_analysis()`` and writes the roofline terms to
``experiments/dryrun/<arch>_<shape>_<mesh>.json``.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, input_specs, supports_shape
from repro.launch import sharding as sh
from repro.launch import steps as st
from repro.launch.mesh import make_production_mesh, node_axes, num_nodes
from repro.launch.roofline import model_flops_for, roofline_terms

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def _mesh_name(multi_pod: bool) -> str:
    return "2x16x16" if multi_pod else "16x16"


def lower_pair(arch: str, shape_name: str, multi_pod: bool, *, compressor: str = "q4b",
               microbatches: int = 1, grad_accum_dtype: str = "float32", attn_chunk: int | None = None,
               seq_shard_attn: bool = False):
    """Build + lower + compile one (arch, shape, mesh). Returns (compiled, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not supports_shape(cfg, shape):
        return None, {"skipped": f"{arch} does not support {shape_name} (full attention; see DESIGN)"}

    if attn_chunk is not None:
        from repro.models import layers as _layers

        _layers.CHUNK_THRESHOLD = attn_chunk
    if seq_shard_attn:
        from repro.models import layers as _layers

        _layers.SEQ_SHARD_AXIS = "model"
    mesh = make_production_mesh(multi_pod=multi_pod)
    lead = ("pod", "data") if multi_pod else ("data",)

    with mesh:
        if shape.step == "train":
            m = num_nodes(mesh)
            trainer = st.make_trainer(cfg, m, compressor=compressor, track_average=False,
                                      microbatches=microbatches, grad_accum_dtype=grad_accum_dtype,
                                      spmd_axis_name=(lead if seq_shard_attn else None))
            state_abs = st.abstract_trainer_state(trainer, cfg)
            pspec = sh.param_pspecs(state_abs.theta, mesh, node_axes=lead)
            state_spec = sh.trainer_state_pspecs(state_abs, pspec, mesh, lead)
            batch_abs = input_specs(cfg, shape_name, num_nodes=m)
            batch_spec = sh.batch_pspecs(batch_abs, mesh, lead_axes=lead)
            jitted = jax.jit(
                trainer.step_impl,
                in_shardings=(sh.shardings(mesh, state_spec), sh.shardings(mesh, batch_spec)),
                donate_argnums=0,
            )
            lowered = jitted.lower(state_abs, batch_abs)
        elif shape.step == "prefill":
            params_abs = st.abstract_params(cfg)
            pspec = sh.param_pspecs(params_abs, mesh)
            batch_abs = input_specs(cfg, shape_name)
            batch_spec = sh.batch_pspecs(batch_abs, mesh, lead_axes=lead)
            step = st.make_prefill_step(cfg, cache_len=shape.seq_len)
            jitted = jax.jit(
                step,
                in_shardings=(sh.shardings(mesh, pspec), sh.shardings(mesh, batch_spec)),
            )
            lowered = jitted.lower(params_abs, batch_abs)
        else:  # decode
            params_abs = st.abstract_params(cfg)
            pspec = sh.param_pspecs(params_abs, mesh)
            cache_abs = st.abstract_cache(cfg, shape.global_batch, shape.seq_len)
            cache_spec = sh.cache_pspecs(cache_abs, mesh, shape.global_batch, lead_axes=lead)
            dec = input_specs(cfg, shape_name)
            tok_spec = sh.batch_pspecs({"tokens": dec["tokens"]}, mesh, lead_axes=lead)["tokens"]
            step = st.make_decode_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(
                    sh.shardings(mesh, pspec),
                    sh.shardings(mesh, cache_spec),
                    sh.shardings(mesh, tok_spec),
                    sh.shardings(mesh, jax.sharding.PartitionSpec()),
                ),
                donate_argnums=1,
            )
            lowered = jitted.lower(params_abs, cache_abs, dec["tokens"], dec["pos"])

        t0 = time.time()
        compiled = lowered.compile()
        meta = {
            "arch": arch,
            "shape": shape_name,
            "mesh": _mesh_name(multi_pod),
            "compile_s": round(time.time() - t0, 1),
        }
        return compiled, meta


def run_pair(arch: str, shape_name: str, multi_pod: bool, *, verbose: bool = True, compressor: str = "q4b", tag: str = "", **lower_kw):
    cfg = get_config(arch)
    arch = cfg.name  # canonical id (e.g. "qwen3-1.7b")
    shape = SHAPES[shape_name]
    try:
        compiled, meta = lower_pair(arch, shape_name, multi_pod, compressor=compressor, **lower_kw)
    except Exception as e:
        traceback.print_exc()
        return {"arch": arch, "shape": shape_name, "mesh": _mesh_name(multi_pod), "error": f"{type(e).__name__}: {e}"}
    if compiled is None:
        if verbose:
            print(f"SKIP {arch} x {shape_name}: {meta['skipped']}")
        return {"arch": arch, "shape": shape_name, "mesh": _mesh_name(multi_pod), **meta}

    chips = 512 if multi_pod else 256
    report = roofline_terms(
        compiled,
        arch=arch,
        shape=shape_name,
        mesh_name=meta["mesh"],
        chips=chips,
        model_flops=model_flops_for(cfg, shape),
    )
    row = report.row()
    row["compile_s"] = meta["compile_s"]
    if tag:
        row["tag"] = tag

    if verbose:
        try:
            print(compiled.memory_analysis())
        except Exception as e:
            print(f"(memory_analysis unavailable: {e})")
        cost = compiled.cost_analysis()
        print({k: v for k, v in sorted(cost.items()) if k in ("flops", "bytes accessed")})
        print(
            f"{arch} x {shape_name} @ {meta['mesh']}: "
            f"compute={report.compute_s*1e3:.2f}ms memory={report.memory_s*1e3:.2f}ms "
            f"collective={report.collective_s*1e3:.2f}ms dominant={report.dominant} "
            f"useful_flops={report.useful_flops_frac:.2%} (compiled in {meta['compile_s']}s)"
        )

    os.makedirs(OUT_DIR, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    fname = os.path.join(OUT_DIR, f"{arch.replace('.', '_')}_{shape_name}_{meta['mesh']}{suffix}.json")
    with open(fname, "w") as f:
        json.dump(row, f, indent=1)
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES), help="input shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true", help="use the 2x16x16 512-chip mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="every (arch x shape)")
    ap.add_argument("--compressor", default="q4b")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="", help="variant tag for perf experiments")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-accum-dtype", default="float32")
    ap.add_argument("--attn-chunk", type=int, default=None,
                    help="override layers.CHUNK_THRESHOLD (query-chunked attention)")
    ap.add_argument("--seq-shard-attn", action="store_true",
                    help="context-parallel attention: shard the query-seq dim over `model`")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else [a.replace("_", "-") for a in ARCHS]
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                suffix = f"_{args.tag}" if args.tag else ""
                fname = os.path.join(
                    OUT_DIR, f"{arch.replace('.', '_')}_{shape}_{_mesh_name(mp)}{suffix}.json"
                )
                if args.skip_existing and os.path.exists(fname):
                    print(f"EXISTS {arch} x {shape} @ {_mesh_name(mp)}")
                    continue
                results.append(run_pair(arch, shape, mp, compressor=args.compressor, tag=args.tag,
                                        microbatches=args.microbatches,
                                        grad_accum_dtype=args.grad_accum_dtype,
                                        attn_chunk=args.attn_chunk,
                                        seq_shard_attn=args.seq_shard_attn))

    errs = [r for r in results if "error" in r]
    print(f"\n== dry-run summary: {len(results) - len(errs)}/{len(results)} OK ==")
    for r in errs:
        print(f"FAIL {r['arch']} x {r['shape']} @ {r['mesh']}: {r['error']}")


if __name__ == "__main__":
    main()
