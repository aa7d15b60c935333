"""Every file of the benchmark loads, and BENCHMARK.json keeps its shape."""
from __future__ import annotations

import json
import re

import pytest
from tiny_cells import BENCH, ROOT

from lpabench import spec

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(cell):
    c = spec.load_cell(cell)
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    assert c.workload["config"] == entry["config"]
    assert c.traffic["kind"] == entry["traffic"]
    assert c.chips == entry["chips"]
    assert spec.traffic_module(c).setup
    assert spec.generator(BENCH, c.config["generator"]).generate
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("config", BENCHMARK["configs"],
                         ids=lambda c: c["name"])
def test_config_files_load(config):
    body = json.loads((ROOT / config["file"]).read_text())
    assert body["name"] == config["name"]
    assert body["reduced"] == config["reduced"]
    assert set(config["reduced"]) <= set(body["params"])


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_readers_exist(metric):
    assert spec.metric_reader(BENCH, metric["name"]).read
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert metric["moves"] in e2e


def test_benchmark_shape():
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    assert set(BENCHMARK) == keys
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    assert BENCHMARK["paths"] == ["bench"]
    every = (BENCHMARK["configs"] + BENCHMARK["workloads"]
             + BENCHMARK["end_to_end"] + BENCHMARK["per_layer"])
    assert all(NAME.match(x["name"]) for x in every)
    assert len({x["name"] for x in every}) == len(every)
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in BENCHMARK["per_layer"]}
    perf = (ROOT / "PERF.md").read_text()
    assert all(layer in perf for layer in layers)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
