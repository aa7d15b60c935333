"""Tiny copies of the benchmark's cells for CPU tests.

``tiny_bench(tmp)`` copies the benchmark's traffic modules, generators and
metric readers into ``tmp``, adds tiny configurations and workloads there,
and returns ``(bench_dir, benchmark)``: the directory and a
``BENCHMARK.json`` whose per-cell lists name the tiny cells.  Nothing of the
repository's ``bench/`` is edited: a new cell is new files.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "graph500-s18.oneshot": ("tiny-g500.oneshot", "graph500", {"scale": 10}),
    "road-256.oneshot": ("tiny-road.oneshot", "road-osm", {"side": 32}),
}


def tiny_bench(tmp: Path):
    tmp = Path(tmp)
    for d in ("traffic", "generators", "metrics"):
        shutil.copytree(BENCH / d, tmp / d)
    (tmp / "configs").mkdir()
    (tmp / "workloads").mkdir()
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell, (tiny, config, params) in TINY.items():
        cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
        cfg["name"] = f"tiny-{config}-{tiny}"
        cfg["params"].update(params)
        (tmp / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
        wl = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
        wl["config"] = cfg["name"]
        (tmp / "workloads" / f"{tiny}.json").write_text(json.dumps(wl))
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY[w][0] for w in m["workloads"]]
    return tmp, benchmark


def run_tiny(tmp: Path, cell: str, trace: bool = False, seconds: float = 1.0,
             seed: int = 2**31 + 11):
    """One CPU run of a tiny cell through the harness; returns the result."""
    import time

    from lpabench import harness, spec
    bench_dir, benchmark = tiny_bench(tmp)
    c = spec.load_cell(cell, bench_dir, benchmark)
    return harness.execute(c, seed, seconds, trace, time.perf_counter(),
                           allow_cpu=True)
