"""Find a cell's files by name and load its plug-ins.

Everything that belongs to one configuration, traffic mix, generator or
per-layer metric sits in a file of its own under the benchmark directory:

    configs/<config>.json       sizes, source, ``reduced``, ``assumed``
    workloads/<cell>.json       config, traffic kind and parameters, chips,
                                and optionally ``params``: sizes that
                                override the configuration's, each a key
                                of its ``reduced`` list
    traffic/<kind>.py           the code of one traffic kind
    generators/<family>.py      one graph family's generator
    metrics/<metric>.py         the reader of one per-layer metric

``BENCHMARK.json`` at the checkout's root says which metrics a cell reports.
Adding a cell, a configuration or a metric is adding files; nothing here
names one.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    bench_dir: Path
    end_to_end: list = field(default_factory=list)   # BENCHMARK.json entries
    per_layer: list = field(default_factory=list)

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def traffic(self) -> dict:
        return self.workload["traffic"]


def read_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _plugin(path: Path, prefix: str):
    """Import a plug-in file by path under a private module name."""
    if not path.is_file():
        raise FileNotFoundError(f"no such plug-in: {path}")
    mod_name = prefix + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def traffic_module(cell: Cell):
    return _plugin(cell.bench_dir / "traffic" / f"{cell.traffic['kind']}.py",
                   "lpabench_traffic_")


def generator(bench_dir: Path, family: str):
    return _plugin(bench_dir / "generators" / f"{family}.py",
                   "lpabench_generator_")


def metric_reader(bench_dir: Path, name: str):
    return _plugin(bench_dir / "metrics" / f"{name}.py", "lpabench_metric_")


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def effective_config(workload: dict, config: dict) -> dict:
    """The configuration with the workload's ``params`` merged into its
    ``params``.  A workload may only move the configuration's cuts of
    scale (its ``reduced`` keys), never a shape such as a degree, a weight
    rule or a generator's initiator."""
    params = workload.get("params", {})
    for key in params:
        if key not in config["reduced"]:
            raise ValueError(
                f"workload params key {key!r} is not in configuration "
                f"{config['name']!r}'s reduced list {config['reduced']}")
    return {**config, "params": {**config["params"], **params}}


def load_cell(name: str, bench_dir: Path = BENCH_DIR,
              benchmark: dict | None = None) -> Cell:
    """The cell's workload and configuration, and the metrics it reports.

    ``Cell.config["params"]`` are the effective sizes: the configuration's,
    overridden by the workload's ``params`` (``effective_config``).
    ``benchmark`` defaults to ``BENCHMARK.json`` beside ``bench_dir``; a cell
    that the file does not list reports ``setup_s`` and every end-to-end
    metric without a ``workloads`` key.
    """
    workload = read_json(bench_dir / "workloads" / f"{name}.json")
    config = effective_config(workload, read_json(
        bench_dir / "configs" / f"{workload['config']}.json"))
    if benchmark is None:
        path = bench_dir.parent / "BENCHMARK.json"
        benchmark = read_json(path) if path.is_file() else {}
    e2e = [m for m in benchmark.get("end_to_end", [])
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in benchmark.get("per_layer", [])
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, workload=workload, config=config,
                bench_dir=bench_dir, end_to_end=e2e, per_layer=per_layer)
