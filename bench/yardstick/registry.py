"""Find a cell, its configuration, its traffic, its kind and its metrics by name.

Everything that belongs to one cell, configuration, traffic mix or per-layer
metric sits in a file of its own; adding one of them is adding a file:

* ``bench/workloads/<cell>.json``   -- config name, traffic name, chips, rate,
  and the limits of the comparison that decides ``correct``;
* ``bench/configs/<config>.json``   -- the model's sizes (its public
  ``config.json`` keys) and the deployment it stands for (``kind``, trainer or
  engine settings);
* ``bench/traffic/<traffic>.json``  -- parameters of the one general generator;
* ``bench/kinds/<kind>.py``         -- the loop of one kind of cell (train, serve);
* ``bench/metrics/<metric>.py``     -- one per-layer metric's reader.

``BENCHMARK.json`` at the root names the cells, metrics and bounds.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = BENCH  # where workloads/, configs/ and traffic/ are looked up
BENCHMARK_FILE = ROOT / "BENCHMARK.json"
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def check_name(name: str) -> str:
    if not NAME_RE.match(name):
        raise ValueError(f"not a valid name: {name!r}")
    return name


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(BENCHMARK_FILE)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    workload: dict  # bench/workloads/<name>.json
    config: dict  # bench/configs/<workload.config>.json
    traffic: dict  # bench/traffic/<workload.traffic>.json

    @property
    def kind(self) -> str:
        return self.config["kind"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def limits(self) -> dict:
        return self.workload["limits"]


def load_cell(name: str) -> Cell:
    wl = load_json(DATA / "workloads" / f"{check_name(name)}.json")
    conf = load_json(DATA / "configs" / f"{check_name(wl['config'])}.json")
    traffic = load_json(DATA / "traffic" / f"{check_name(wl['traffic'])}.json")
    if traffic["kind"] != conf["kind"]:
        raise ValueError(f"{name}: traffic kind {traffic['kind']!r} does not "
                         f"match config kind {conf['kind']!r}")
    return Cell(name, wl, conf, traffic)


def load_kind(kind: str):
    return importlib.import_module(f"kinds.{check_name(kind)}")


def load_metric(name: str):
    """The reader module of per-layer metric ``name`` (bench/metrics/<name>.py);
    it defines ``read(run) -> float | None``."""
    path = BENCH / "metrics" / f"{check_name(name)}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _listed(entry: dict, cell: str, e2e_of_cell: set[str]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves") in e2e_of_cell if "moves" in entry else True


def metrics_of(bench: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """(end-to-end, per-layer) metric entries of BENCHMARK.json that ``cell``
    reports."""
    e2e = [m for m in bench["end_to_end"] if _listed(m, cell, set())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _listed(m, cell, names)]
    return e2e, layer
