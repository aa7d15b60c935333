"""Read the controls of a cell's comparison at the cell's own size.

    python3 bench/control.py --workload road-256.oneshot --seeds 11,12,13

The comparison that decides ``correct`` counts the vertices whose label
differs from the plain reference (limit 0).  For each seed this prints that
count for two controls put in the program's place, on exactly the inputs a
run of the cell with that seed makes:

* ``bfloat16``: the reference with its per-label weight sums formed and
  compared in bfloat16, the precision below the configuration's float32;
* ``no_split_last``: the reference without Split-Last, which breaks the
  configuration's guarantee that no community is internally disconnected.

A control has to read above the limit.  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import ml_dtypes  # noqa: E402

from lpabench import harness, reference, spec  # noqa: E402


def inputs(cell, seed: int) -> list:
    run = harness.Run(cell=cell, seed=seed, seconds=0.0, trace=False,
                      t_process=time.perf_counter())
    return spec.traffic_module(cell).make_inputs(run)


def unsplit(n: int, edges, weights):
    """The reference without Split-Last: the propagated labels, compacted
    as the system compacts its answer.  This is what the system would
    return with Split-Last left out: communities keep the name propagation
    gave them instead of their smallest vertex id, and a disconnected one
    stays whole."""
    comm, _ = reference.propagate(reference.DirectedCsr(n, edges, weights))
    return reference.compact(comm)


def readings(graphs: list) -> dict:
    out = {"bfloat16": 0, "no_split_last": 0, "graphs": len(graphs)}
    for n, edges, weights in graphs:
        expected, _ = reference.detect(n, edges, weights)
        low, _ = reference.detect(n, edges, weights,
                                  dtype=ml_dtypes.bfloat16)
        out["bfloat16"] += reference.mismatched_vertices(low, expected)
        out["no_split_last"] += reference.mismatched_vertices(
            unsplit(n, edges, weights), expected)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = readings(inputs(cell, seed))
        print(json.dumps({"workload": args.workload, "seed": seed, **out,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
