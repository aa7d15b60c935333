"""Paper Figure 6: strong scaling of GSL-LPA (propagation + split phases).

The paper scales threads 1..64 on a dual-Xeon.  This container has ONE
physical core, so wall-clock "scaling" over virtual devices measures
partitioning overhead, not speedup.  What this benchmark therefore reports
per device count is (a) the per-device work (rows x d_max) — perfectly
balanced by construction, (b) the collective bytes per sweep
(n x 4B label all-gather) — the structural scaling terms that the §Roofline
analysis converts into time on real hardware — plus the (overhead-dominated)
CPU wall time for completeness.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmarks.common import emit

REPO = Path(__file__).resolve().parents[1]

_CHILD = r"""
import json, time
import jax, numpy as np
from repro.core.distributed import distributed_gsl_lpa, shard_graph
from repro.graphgen import rmat

ndev = {ndev}
from repro.parallel.compat import make_mesh
mesh = make_mesh((ndev,), ("data",))
g = rmat(11, 12, seed=7)
t0 = time.time()
labels, it, sit = distributed_gsl_lpa(g, mesh)
dt = time.time() - t0
sg = shard_graph(g, mesh)
print("RESULT" + json.dumps({{
    "seconds": dt, "lpa_iters": it, "split_iters": sit,
    "rows_per_device": sg.n_pad // ndev, "d_max": sg.d_max,
    "allgather_bytes_per_sweep": int(sg.n_pad * 4),
    "n": g.n, "edges": g.num_edges}}))
"""


def run(quiet: bool = False, device_counts=(1, 2, 4, 8)) -> list[dict]:
    rows = []
    base = None
    for ndev in device_counts:
        # the children emulate devices on the host CPU: they never claim
        # an accelerator, which belongs to the parent process
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
                   PYTHONPATH=str(REPO / "src"))
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD.format(ndev=ndev)],
            env=env, capture_output=True, text=True, timeout=560)
        if proc.returncode != 0:
            raise RuntimeError(f"ndev={ndev} child failed: "
                               f"{proc.stderr.strip()[-2000:]}")
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("RESULT")][0]
        r = json.loads(line[len("RESULT"):])
        if base is None:
            base = r["seconds"]
        rows.append({
            "bench": f"ndev{ndev}", "seconds": r["seconds"],
            "rel_time": round(r["seconds"] / base, 3),
            "rows_per_device": r["rows_per_device"],
            "work_scaling": round(
                rows[0]["rows_per_device"] / r["rows_per_device"], 2)
            if rows else 1.0,
            "allgather_bytes_per_sweep": r["allgather_bytes_per_sweep"],
            "iters": r["lpa_iters"] + r["split_iters"],
        })
    if not quiet:
        emit(rows, "fig6_scaling")
    return rows


if __name__ == "__main__":
    run()
