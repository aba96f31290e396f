"""The harness finds every cell, configuration, traffic mix, kind and metric
by name, and a new cell is picked up from an added file alone."""
import json
import re
import shutil

import pytest

from yardstick import registry

BENCH = registry.benchmark()
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_every_listed_piece_has_its_file():
    for w in BENCH["workloads"]:
        cell = registry.load_cell(w["name"])
        assert (cell.workload["config"], cell.workload["traffic"], cell.chips) == (
            w["config"], w["traffic"], w["chips"])
        assert (registry.BENCH / "kinds" / f"{cell.kind}.py").exists()
        for name in cell.limits:
            assert cell.limits[name] is not None, (w["name"], name)
    for c in BENCH["configs"]:
        assert (registry.ROOT / c["file"]).exists()
        conf = registry.load_json(registry.ROOT / c["file"])
        assert set(c["reduced"]) == set(conf["reduced"])
    for m in BENCH["per_layer"]:
        assert hasattr(registry.load_metric(m["name"]), "read")


def test_names_and_units():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert registry.NAME_RE.match(n)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for w in BENCH["workloads"]:
        e2e, layer = registry.metrics_of(BENCH, w["name"])
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2 and layer
        assert all(m["moves"] in names for m in layer)


def test_new_cell_from_an_added_file(tmp_path, monkeypatch):
    data = tmp_path / "bench"
    for sub in ("workloads", "configs", "traffic"):
        shutil.copytree(registry.BENCH / sub, data / sub)
    wl = registry.load_json(registry.BENCH / "workloads" / "serve-q4b-chat.json")
    (data / "workloads" / "serve-q4b-chat-over.json").write_text(
        json.dumps({**wl, "rate_rps": 1.25 * wl["rate_rps"]}))
    monkeypatch.setattr(registry, "DATA", data)
    cell = registry.load_cell("serve-q4b-chat-over")
    assert cell.kind == "serve" and cell.workload["rate_rps"] == 1.25 * wl["rate_rps"]
    bench = {**BENCH, "workloads": BENCH["workloads"] + [
        {"name": "serve-q4b-chat-over", "config": "qwen3-4b-serve", "traffic": "chat", "chips": 1}]}
    e2e, layer = registry.metrics_of(bench, "serve-q4b-chat-over")
    assert [m["name"] for m in e2e] == ["setup_s"]  # until BENCHMARK.json lists it
    assert layer == []


def test_names_cannot_leave_their_directory():
    with pytest.raises(ValueError):
        registry.load_cell("../BENCHMARK")
