"""Tiny copies of the benchmark's cells for CPU tests.

Every cell of ``BENCHMARK.json`` has a tiny file ``tests/tiny/<cell>.json``
beside this module::

    {"name": "<tiny cell>", "params": {...}, "traffic": {...}}

``name`` names the tiny copy; ``params`` override the sizes of the cell's
configuration and of its workload; ``traffic`` (optional) overrides keys of
the workload's traffic.  ``tiny_bench(tmp)`` copies the benchmark's traffic
modules, generators and metric readers into ``tmp``, writes there a tiny
configuration and workload for each cell that has a tiny file, and returns
``(bench_dir, benchmark)``: the directory and a ``BENCHMARK.json`` whose
per-cell lists name the tiny copies.  Nothing here names a cell and nothing
of the repository's ``bench/`` is written: a new cell is new files.
"""
from __future__ import annotations

import copy
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_dir(bench_dir: Path = BENCH) -> Path:
    return bench_dir / "tests" / "tiny"


def tiny_files(benchmark: dict = BENCHMARK, bench_dir: Path = BENCH) -> dict:
    """cell -> its tiny file, for each cell of ``benchmark`` that has one."""
    out = {}
    for w in benchmark["workloads"]:
        path = tiny_dir(bench_dir) / f"{w['name']}.json"
        if path.is_file():
            out[w["name"]] = json.loads(path.read_text())
    return out


def tiny_names(benchmark: dict = BENCHMARK) -> list:
    """The tiny copies of the cells, in ``BENCHMARK.json``'s order."""
    return [t["name"] for t in tiny_files(benchmark).values()]


def tiny_bench(tmp: Path, source: Path = BENCH, benchmark: dict | None = None):
    """Tiny copies of ``source``'s cells in ``tmp``; ``benchmark`` defaults
    to the repository's ``BENCHMARK.json``."""
    tmp = Path(tmp)
    benchmark = copy.deepcopy(BENCHMARK if benchmark is None else benchmark)
    for d in ("traffic", "generators", "metrics"):
        shutil.copytree(source / d, tmp / d)
    (tmp / "configs").mkdir()
    (tmp / "workloads").mkdir()
    tiny = tiny_files(benchmark, source)
    for cell, t in tiny.items():
        wl = json.loads((source / "workloads" / f"{cell}.json").read_text())
        cfg = json.loads(
            (source / "configs" / f"{wl['config']}.json").read_text())
        params = t.get("params", {})
        cfg["name"] = f"tiny-{cfg['name']}-{t['name']}"
        cfg["params"].update(params)
        (tmp / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
        # the tiny sizes win over the workload's own
        wl["params"] = {k: v for k, v in wl.get("params", {}).items()
                        if k not in params}
        wl["config"] = cfg["name"]
        wl["traffic"].update(t.get("traffic", {}))
        (tmp / "workloads" / f"{t['name']}.json").write_text(json.dumps(wl))
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [tiny[w]["name"] for w in m["workloads"]
                              if w in tiny]
    return tmp, benchmark


def run_tiny(tmp: Path, cell: str, trace: bool = False, seconds: float = 1.0,
             seed: int = 2**31 + 11, source: Path = BENCH,
             benchmark: dict | None = None):
    """One CPU run of a tiny cell through the harness; returns the result."""
    import time

    from lpabench import harness, spec
    bench_dir, benchmark = tiny_bench(tmp, source, benchmark)
    c = spec.load_cell(cell, bench_dir, benchmark)
    return harness.execute(c, seed, seconds, trace, time.perf_counter(),
                           allow_cpu=True)
