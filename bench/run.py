#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name: ``bench/workloads/<cell>.json`` names its
configuration (``bench/configs``) and traffic (``bench/traffic``), and the
configuration's ``kind`` names the loop (``bench/kinds/<kind>.py``). With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of the window is reduced to its per-layer
metrics (``bench/metrics/<metric>.py``, as ``BENCHMARK.json`` lists them).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``checks``, each compared number beside its limit. The same
numbers close standard error, after a ``diag`` line (set-up seconds, and for a
serving cell where the gap percentiles fall). Without a TPU, or with fewer
chips than the cell asks for, it prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from yardstick import compare, device, registry  # noqa: E402
from yardstick import trace as tr  # noqa: E402
from yardstick.peaks import peaks_for  # noqa: E402

TRACE_DIR = registry.ROOT / ".bench_trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _check_listing(bench: dict, cell) -> None:
    entry = next((w for w in bench["workloads"] if w["name"] == cell.name), None)
    if entry is None:
        raise SystemExit(f"{cell.name} is not a cell of BENCHMARK.json")
    for key in ("config", "traffic", "chips"):
        if entry[key] != cell.workload[key]:
            raise SystemExit(f"{cell.name}: BENCHMARK.json {key}={entry[key]!r} but "
                             f"the workload file says {cell.workload[key]!r}")


def layer_metrics(run, entries, red) -> dict:
    run.reduction = red
    run.peaks = peaks_for(run.devices[0].device_kind)
    out = {}
    for m in entries:
        value = registry.load_metric(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, t0: float) -> dict:
    """Drive one run of ``cell`` on ``devices``; returns the result object."""
    bench = registry.benchmark()
    e2e_entries, layer_entries = registry.metrics_of(bench, cell.name)
    kind = registry.load_kind(cell.kind)
    out = kind.run(cell, seed, seconds, TRACE_DIR if trace else None, devices, t0)
    ok, checks = compare.judge(out["numbers"], cell.limits)
    result = {
        "correct": bool(ok and out["failed"] == 0),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": {},
        "device": {**device.describe(devices),
                   "memory_peak_bytes": out["run"].memory_peak_bytes},
    }
    if trace:
        red = tr.reduce(tr.load(TRACE_DIR))
        result["metrics"] = layer_metrics(out["run"], layer_entries, red)
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()
        del red
        gc.collect()
    else:
        for m in e2e_entries:
            result["metrics"][m["name"]] = {"value": float(out["e2e"][m["name"]]),
                                           "unit": m["unit"]}
    result["checks"] = checks
    result["diag"] = {"setup_s": out["setup_s"], **out.get("diag", {})}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    cell = registry.load_cell(args.workload)
    _check_listing(registry.benchmark(), cell)
    device.configure_cache()
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoChip as e:
        print(f"bench: {e}; this benchmark runs on the chip only", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, T0)
    print(f"diag {json.dumps(result.pop('diag'))}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    for m in result["metrics"].values():
        if not math.isfinite(m["value"]):
            raise SystemExit(f"non-finite metric in {result['metrics']}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
