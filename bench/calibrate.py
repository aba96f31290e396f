#!/usr/bin/env python3
"""Readings that set a cell's limits, and the serving knee sweep (on the chip).

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 5 \
        [--control] [--faults half_batch,no_exchange,zero_payload,token] [--rates 1.5,2] \
        [--out FILE]

For each seed it drives a whole run of the cell (the same kinds code the
benchmark runs, with a window of ``--seconds``) and prints one JSON line: the
program's numbers against the reference (the lower readings); with
``--control`` the same numbers for the reference put in the program's place at
float8 (the control); with ``--faults`` the numbers for each planted fault;
and the run's end-to-end metrics; for a training cell also the reading, node
and leaf at which each leaf number is widest. ``--rates`` runs a serving cell at each of
these rates instead of its own (the knee sweep). Limits are set from these readings by hand, never here.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from yardstick import adgda_ref, compare, device, loadgen, registry, serve_ref, weights  # noqa: E402


def train_extra(cell, seed, out, devices, control, faults):
    model, train, traffic = cell.config["model"], cell.config["train"], cell.traffic
    key = weights.seed_key(seed)
    wkey, nkey = jax.random.fold_in(key, 0), jax.random.fold_in(key, 2)
    batches = loadgen.train_batches(traffic, train["nodes"], model["vocab_size"], 3, seed)
    ref = out["reference"]
    got = {}
    runs = ([("control", dict(prec="fp8"))] if control else []) + [
        (f, dict(fault=f)) for f in faults]
    for name, kw in runs:
        alt = adgda_ref.run(model, train, wkey, batches, jax.random.fold_in(nkey, 7),
                            devices, **kw)
        got[name] = compare.train_numbers(alt, ref)
        del alt
        gc.collect()
    return got


def serve_extra(cell, seed, out, devices, control, faults):
    model = cell.config["model"]
    wkey = jax.random.fold_in(weights.seed_key(seed), 0)
    got = {}
    if control:
        got["control"] = {"token_gap": serve_ref.control_gap(model, wkey, out["sample"], devices[0])}
    if "token" in faults and out["sample"]:
        prompt, served = out["sample"][0]
        altered = [(prompt, [(served[0] + 1) % model["vocab_size"]] + list(served[1:]))]
        got["token"] = {"token_gap": serve_ref.token_gap(model, wkey, altered, devices[0])}
    return got


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    ap.add_argument("--rates", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    base = registry.load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    device.configure_cache()
    devices = device.require_tpu(base.chips)
    kind = registry.load_kind(base.kind)
    faults = [f for f in args.faults.split(",") if f]
    extra = train_extra if base.kind == "train" else serve_extra
    sink = open(args.out, "a") if args.out else None
    if base.kind == "train":
        names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
            jax.eval_shape(lambda k: weights.make(base.config["model"], k),
                           jax.random.PRNGKey(0)))[0]]
    t0 = T0
    runs = [(r, int(s)) for r in rates for s in args.seeds.split(",")]
    for rate, seed in runs:
        cell = base if rate is None else dataclasses.replace(
            base, workload={**base.workload, "rate_rps": rate})
        out = kind.run(cell, seed, args.seconds, None, devices, t0)
        line = {"cell": cell.name, "seed": seed, "rate": cell.workload.get("rate_rps"),
                "program": out["numbers"], "e2e": out["e2e"], "attempted": out["attempted"],
                "failed": out["failed"], "memory_peak_bytes": out["run"].memory_peak_bytes,
                "diag": out.get("diag")}
        line.update(extra(cell, seed, out, devices, args.control, faults))
        if cell.kind == "train":
            line["ref_losses"] = np.asarray(out["reference"]["losses"]).tolist()
            line["last_losses"] = out["run"].last_losses
            line["worst"] = {k: [read, node, names[leaf]] for k, (read, node, leaf) in
                             compare.worst_leaves(out["prog"], out["reference"]).items()}
        text = json.dumps(line, default=float)
        print(text, flush=True)
        if sink:
            sink.write(text + "\n")
            sink.flush()
        del out
        gc.collect()
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
