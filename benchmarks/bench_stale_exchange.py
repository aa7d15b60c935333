"""§Perf evidence: stale-label exchange (exchange_every=k) quality trade-off.

Runs the distributed engine on 8 virtual devices (subprocess) over a
planted-partition graph and reports modularity + disconnected fraction for
k = 1 (paper-faithful), 2, 4.  Volume scales 1/k by construction (§Perf
cell 1); this benchmark quantifies the quality side of the trade.
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
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import modularity, disconnected_fraction
from repro.core.distributed import distributed_gsl_lpa
from repro.graphgen import planted_partition

from repro.parallel.compat import make_mesh
mesh = make_mesh((8,), ("data",))
g, truth = planted_partition(20, 100, p_in=0.2, p_out=0.001, seed=9)
out = {}
for k in (1, 2, 4):
    labels, it, sit = distributed_gsl_lpa(g, mesh, exchange_every=k)
    lab = jnp.asarray(labels)
    out[str(k)] = {
        "Q": float(modularity(g, lab)),
        "disc": float(disconnected_fraction(g, lab)),
        "iters": it,
        "allgathers_per_iter": 2.0 / k,
    }
print("RESULT" + json.dumps(out))
"""


def run(quiet: bool = False) -> list[dict]:
    # the child emulates devices on the host CPU: it never claims an
    # accelerator, which belongs to the parent process
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=560)
    if proc.returncode != 0:
        raise RuntimeError(f"child failed: {proc.stderr.strip()[-2000:]}")
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT")][0]
    res = json.loads(line[len("RESULT"):])
    rows = []
    for k, r in res.items():
        rows.append({
            "bench": f"exchange_every_{k}", "seconds": 0.0,
            "Q": round(r["Q"], 4), "disc_frac": round(r["disc"], 5),
            "iters": r["iters"],
            "label_allgathers_per_iter": r["allgathers_per_iter"],
        })
    if not quiet:
        emit(rows, "stale_exchange")
    return rows


if __name__ == "__main__":
    run()
