"""Every file of the benchmark loads, and BENCHMARK.json keeps its shape.

The checks are functions of a benchmark directory and its
``BENCHMARK.json``, so that a cell built in a temporary directory
(test_bench_run.py) is held to the same checks as the repository's.
"""
from __future__ import annotations

import json
import re

import pytest
from tiny_cells import BENCH, BENCHMARK, ROOT, tiny_dir, tiny_files

from lpabench import graphs, harness, spec

CELLS = [w["name"] for w in BENCHMARK["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
# keys that set a graph's shape, never a cut of its scale
SHAPES = {"avg_degree", "weights", "edge_factor", "a", "b", "c"}


def check_cell(entry: dict, bench_dir=BENCH, benchmark=BENCHMARK):
    c = spec.load_cell(entry["name"], bench_dir, benchmark)
    assert c.workload["config"] == entry["config"]
    assert c.traffic["kind"] == entry["traffic"]
    assert c.chips == entry["chips"]
    assert spec.traffic_module(c).setup
    assert spec.generator(bench_dir, c.config["generator"]).generate
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    return c


def check_config(config: dict, root=ROOT):
    body = json.loads((root / config["file"]).read_text())
    assert body["name"] == config["name"]
    assert body["reduced"] == config["reduced"]
    assert set(config["reduced"]) <= set(body["params"])
    assert not set(config["reduced"]) & SHAPES


def check_metric(metric: dict, bench_dir=BENCH, benchmark=BENCHMARK):
    assert spec.metric_reader(bench_dir, metric["name"]).read
    e2e = {m["name"] for m in benchmark["end_to_end"]}
    assert metric["moves"] in e2e


def check_benchmark(benchmark: dict, root=ROOT):
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    assert set(benchmark) == keys
    assert benchmark["command"] == ["python3", "bench/run.py"]
    assert benchmark["paths"] == ["bench"]
    every = (benchmark["configs"] + benchmark["workloads"]
             + benchmark["end_to_end"] + benchmark["per_layer"])
    assert all(NAME.match(x["name"]) for x in every)
    assert len({x["name"] for x in every}) == len(every)
    for m in benchmark["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in benchmark["per_layer"]}
    perf = (root / "PERF.md").read_text()
    assert all(layer in perf for layer in layers)
    assert len((root / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(cell):
    check_cell(next(w for w in BENCHMARK["workloads"] if w["name"] == cell))


@pytest.mark.parametrize("config", BENCHMARK["configs"],
                         ids=lambda c: c["name"])
def test_config_files_load(config):
    check_config(config)


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_readers_exist(metric):
    check_metric(metric)


def test_benchmark_shape():
    check_benchmark(BENCHMARK)


def test_every_cell_has_a_tiny_copy():
    missing = [c for c in CELLS if not (tiny_dir() / f"{c}.json").is_file()]
    assert not missing, (f"cells without a tiny copy in {tiny_dir()}: "
                         f"{missing}")
    strays = {p.stem for p in tiny_dir().glob("*.json")} - set(CELLS)
    assert not strays, f"tiny files of no cell: {sorted(strays)}"
    tiny = tiny_files()
    names = [t["name"] for t in tiny.values()]
    assert all(NAME.match(n) for n in names)
    assert len(set(names) | set(CELLS)) == len(names) + len(CELLS)
    for t in tiny.values():
        assert set(t) <= {"name", "params", "traffic"}


def _workload_with(tmp_path, config: str, params: dict):
    """A cell of one workload file, in a copy of the configurations."""
    bench = tmp_path / "bench"
    (bench / "workloads").mkdir(parents=True)
    (bench / "configs").mkdir()
    for cfg in BENCHMARK["configs"]:
        (bench / "configs" / f"{cfg['name']}.json").write_text(
            (ROOT / cfg["file"]).read_text())
    (bench / "workloads" / "probe.json").write_text(json.dumps(
        {"config": config, "traffic": {"kind": "oneshot"}, "chips": 1,
         "params": params, "why": "a probe"}))
    return bench


@pytest.mark.parametrize("config,key", [
    (c["name"], k) for c in BENCHMARK["configs"]
    for k in json.loads((ROOT / c["file"]).read_text())["params"]
    if k not in c["reduced"]] + [(BENCHMARK["configs"][0]["name"], "nodes")])
def test_workload_params_outside_reduced_are_refused(tmp_path, config, key):
    bench = _workload_with(tmp_path, config, {key: 1})
    with pytest.raises(ValueError, match=repr(key)):
        spec.load_cell("probe", bench, {})


@pytest.mark.parametrize("config", BENCHMARK["configs"],
                         ids=lambda c: c["name"])
def test_workload_params_set_the_reduced_keys(tmp_path, config):
    raw = json.loads((ROOT / config["file"]).read_text())
    moved = {k: raw["params"][k] + 1 for k in config["reduced"]}
    c = spec.load_cell("probe", _workload_with(tmp_path, config["name"],
                                               moved), {})
    assert c.config["params"] == {**raw["params"], **moved}
    assert json.loads((ROOT / config["file"]).read_text()) == raw


@pytest.mark.parametrize("cell", CELLS)
def test_effective_params_key_the_graph_cache(cell, monkeypatch):
    """A cell's effective params are its configuration's, overridden by its
    workload's; a workload without ``params`` keeps its configuration's,
    and so the graph-cache key its inputs had before workloads could set
    sizes."""
    c = spec.load_cell(cell)
    raw = json.loads((BENCH / "configs" / f"{c.workload['config']}.json")
                     .read_text())
    assert c.config["params"] == {**raw["params"],
                                  **c.workload.get("params", {})}
    if "params" not in c.workload:
        assert c.config["params"] == raw["params"]
    traffic = spec.traffic_module(c)
    if not hasattr(traffic, "make_inputs"):
        return
    keys = []
    monkeypatch.setattr(graphs, "cached", lambda *a: keys.append(a) or [])
    run = harness.Run(cell=c, seed=2**31 + 5, seconds=0.0, trace=False,
                      t_process=0.0)
    traffic.make_inputs(run)
    (config, name, seed, params, _make), = keys
    count = int(c.traffic.get("graphs", 1))
    assert (config, name, seed) == (raw["name"], cell, 2**31 + 5)
    assert params == {**c.config["params"], "graphs": count}
    if "params" not in c.workload:
        assert graphs._key(config, name, seed, params) == graphs._key(
            raw["name"], cell, seed, {**raw["params"], "graphs": count})
