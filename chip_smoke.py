"""Bring-up smoke run of the detection engine on a TPU chip.

    python chip_smoke.py              # one chip: the four phases below
    python chip_smoke.py --chips 4    # the sharded backend over four chips

Drives the main path through the entry points users call (``Engine.fit``,
``Engine.fit_many`` behind ``MicroBatcher``) at sizes users run, and
checks every answer:

1. graph500-22 (LDBC Graphalytics: Graph500 Kronecker generator, scale 22,
   edge factor 16, a=0.57, b=c=0.19) through the auto policy, which must
   pick ``segment``; no internally-disconnected community, and on-chip
   modularity equal to a float64 host recomputation from the CSR;
2. a road-class 1024x1024 grid (2^20 vertices), which the auto policy must
   send to ``tile`` with its propagate and split programs compiled to
   Mosaic kernels (``tpu_custom_call``); no disconnected community;
3. parity at a mid size: ``segment`` and ``tile`` labels on the chip equal
   each other and the ``segment`` labels computed on the host CPU;
4. served traffic: 32 requests through ``serve_communities`` (micro-batched
   ``fit_many``), each member equal to a solo ``fit``.

With ``--chips 4`` only one bounded-degree graph runs, on the ``sharded``
backend over a four-chip mesh, against its one-chip labels.

Times printed here are smoke timings, not benchmark results.  The script
refuses to run without a TPU, exits non-zero on any failed check, and
prints one JSON object as its last line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

GRAPH500_SCALE = 22
GRAPH500_EDGE_FACTOR = 16
ROAD_GRID_SIDE = 1024        # 2^20 vertices: the largest square grid the
                             # tile admission takes (2048^2 is refused)
PARITY_RMAT_SCALE = 16
PARITY_ER = (1 << 16, 16.0)  # vertices, average degree: tile-admitted
PARITY_GRID_SIDE = 256
SERVE_REQUESTS = 32
SHARDED_GRID_SIDE = 1024
# The on-chip modularity adds in float32 by reductions and pairwise sums
# (core/modularity.py), whose relative error grows with log2 of the term
# count: ~1e-6 at 10^8 edges.  The host recomputes in float64.  1e-5 is
# above that and far below what a saturated or dropped community sum shows
# (a sequential float32 scatter-add was off by 0.78 on graph500-22).
MODULARITY_TOL = 1e-5


# Seconds XLA spent compiling (or loading programs from the persistent
# compile cache), summed from jax.monitoring events.
_COMPILE_S = [0.0]


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[smoke] {phase}: {body}", flush=True)


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += seconds


def fit_timings(res) -> dict[str, str]:
    """The engine's own host-clock split of one fit (compiles included)."""
    return {k: f"{v:.1f}" for k, v in res.timings.items()}


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def mosaic_programs(plan, bucket) -> dict[str, bool]:
    """Whether the tile plan's compiled propagate and split programs
    contain a Mosaic kernel call (no interpret mode, no jnp oracle)."""
    import jax
    import jax.numpy as jnp
    _n, _m, d = bucket
    r = plan.rows
    S = jax.ShapeDtypeStruct
    tiles = (S((r, d), jnp.int32), S((r, d), jnp.float32),
             S((r, d), jnp.bool_))
    n_real = S((), jnp.int32)
    prop = plan.propagate.lower(*tiles, n_real, S((r,), jnp.int32),
                                S((r,), jnp.bool_)).compile().as_text()
    split = plan.split.lower(tiles[0], tiles[2], S((r,), jnp.int32),
                             S((r,), jnp.int32), n_real).compile().as_text()
    return {"propagate": "tpu_custom_call" in prop,
            "split": "tpu_custom_call" in split}


def phase_powerlaw(seed: int) -> None:
    import jax.numpy as jnp
    from repro.core.modularity import modularity, modularity_host
    from repro.engine import Engine, EngineConfig
    from repro.engine.bucketing import max_degree
    from repro.graphgen import rmat

    t0 = time.perf_counter()
    g = rmat(GRAPH500_SCALE, GRAPH500_EDGE_FACTOR, a=0.57, b=0.19, c=0.19,
             seed=seed)
    say("graph500-22", n=g.n, directed_edges=g.num_edges,
        max_degree=max_degree(g),
        host_generation_s=f"{time.perf_counter() - t0:.1f}")
    t0 = time.perf_counter()
    res = Engine(EngineConfig()).fit(g)
    t_fit = time.perf_counter() - t0
    say("graph500-22", backend=res.backend, bucket=res.bucket,
        lpa_iterations=res.lpa_iterations,
        split_iterations=res.split_iterations,
        communities=res.num_communities,
        smoke_fit_s_incl_compile=f"{t_fit:.1f}",
        smoke_timings_s=fit_timings(res), peak_bytes_in_use=peak_bytes())
    check(res.backend == "segment", "auto policy must pick segment for "
          f"graph500-22, picked {res.backend}")
    disc = res.check_connected(g)
    q_dev = float(modularity(g, jnp.asarray(res.labels)))
    q_host = modularity_host(g, res.labels)
    say("graph500-22", disconnected_fraction=disc, modularity_chip=q_dev,
        modularity_host_f64=q_host, abs_diff=abs(q_dev - q_host),
        tol=MODULARITY_TOL, peak_bytes_in_use=peak_bytes())
    check(disc == 0.0, f"graph500-22 disconnected fraction {disc}")
    check(abs(q_dev - q_host) <= MODULARITY_TOL,
          f"modularity {q_dev} on chip vs {q_host} on host")


def phase_road() -> None:
    from repro.engine import CompileCache, Engine, EngineConfig
    from repro.graphgen import grid2d

    g = grid2d(ROAD_GRID_SIDE)
    eng = Engine(EngineConfig(), cache=CompileCache())
    t0 = time.perf_counter()
    res = eng.fit(g)
    t_fit = time.perf_counter() - t0
    say("road-grid", n=g.n, directed_edges=g.num_edges, backend=res.backend,
        bucket=res.bucket, lpa_iterations=res.lpa_iterations,
        split_iterations=res.split_iterations,
        communities=res.num_communities,
        smoke_fit_s_incl_compile=f"{t_fit:.1f}",
        smoke_timings_s=fit_timings(res), peak_bytes_in_use=peak_bytes())
    check(res.backend == "tile", f"auto policy must pick tile for the "
          f"{ROAD_GRID_SIDE}^2 grid, picked {res.backend}")
    (plan,) = eng.cache.plans().values()
    mosaic = mosaic_programs(plan, res.bucket)
    disc = res.check_connected(g)
    say("road-grid", kernel_mode=eng.config.kernel_mode,
        mosaic_kernel_in=mosaic, disconnected_fraction=disc)
    check(all(mosaic.values()), f"tile programs without a Mosaic kernel: "
          f"{mosaic}")
    check(disc == 0.0, f"road grid disconnected fraction {disc}")


def _fit_on(backend: str, graph, device=None):
    import jax
    from repro.engine import CompileCache, Engine, EngineConfig
    eng = Engine(EngineConfig(backend=backend), cache=CompileCache())
    if device is None:
        return eng.fit(graph)
    with jax.default_device(device):
        return eng.fit(jax.device_put(graph, device))


def phase_parity(seed: int) -> None:
    import jax
    import numpy as np
    from repro.graphgen import erdos_renyi, grid2d, rmat

    cpu = jax.devices("cpu")[0]
    graphs = {
        f"rmat{PARITY_RMAT_SCALE}": lambda: rmat(PARITY_RMAT_SCALE, 16,
                                                 seed=seed),
        "er65536": lambda: erdos_renyi(*PARITY_ER, seed=seed),
        f"grid{PARITY_GRID_SIDE}": lambda: grid2d(PARITY_GRID_SIDE),
    }
    for name, make in graphs.items():
        g = make()
        runs = {"segment": _fit_on("segment", g),
                "cpu_segment": _fit_on("segment", g, device=cpu)}
        try:
            runs["tile"] = _fit_on("tile", g)
        except ValueError as e:
            check(name.startswith("rmat"), f"tile refused {name}: {e}")
            say("parity", graph=name, tile_refused=repr(str(e)))
        ref = runs["cpu_segment"]
        same = {k: bool(np.array_equal(r.labels, ref.labels)
                        and r.lpa_iterations == ref.lpa_iterations
                        and r.split_iterations == ref.split_iterations)
                for k, r in runs.items() if k != "cpu_segment"}
        say("parity", graph=name, n=g.n, directed_edges=g.num_edges,
            communities=ref.num_communities,
            lpa_iterations=ref.lpa_iterations,
            bit_identical_to_cpu=same, peak_bytes_in_use=peak_bytes())
        check(all(same.values()), f"{name}: chip labels differ from the "
              f"CPU reference: {same}")


def phase_serving(seed: int) -> None:
    import numpy as np
    from repro.engine import CompileCache, Engine, EngineConfig
    from repro.launch.serve import community_traffic, serve_communities

    records, summary = serve_communities(num_requests=SERVE_REQUESTS,
                                         seed=seed)
    graphs = community_traffic(SERVE_REQUESTS, seed=seed)
    solo = Engine(EngineConfig(), cache=CompileCache())
    mismatched = [i for i, (g, r) in enumerate(zip(graphs, records))
                  if not np.array_equal(solo.fit(g).labels, r["labels"])]
    batched_tile = sum(r["batch_size"] > 1 and r["backend"] == "tile"
                       for r in records)
    say("serving", requests=len(records),
        batch_sizes=dict(summary["batch_size_hist"]),
        backends=sorted({r["backend"] for r in records}),
        members_in_tile_batches=batched_tile,
        members_differing_from_solo=mismatched,
        smoke_p50_ms=f"{summary['p50_ms']:.1f}",
        smoke_p95_ms=f"{summary['p95_ms']:.1f}",
        peak_bytes_in_use=peak_bytes())
    check(len(records) == SERVE_REQUESTS, "requests lost")
    check(batched_tile > 0, "no batch of size > 1 ran on tile")
    check(not mismatched, f"batched members {mismatched} differ from solo")


def phase_sharded() -> None:
    import jax
    import numpy as np
    from repro.graphgen import grid2d

    check(jax.device_count() == 4, f"--chips 4 sees {jax.device_count()} "
          f"devices")
    g = grid2d(SHARDED_GRID_SIDE)
    # segment and tile labels are bit-identical on one chip (the parity
    # phase); segment is the cheaper one-chip reference for this grid
    one = _fit_on("segment", g)
    t0 = time.perf_counter()
    four = _fit_on("sharded", g)
    t_fit = time.perf_counter() - t0
    same = bool(np.array_equal(one.labels, four.labels)
                and one.lpa_iterations == four.lpa_iterations
                and one.split_iterations == four.split_iterations)
    say("sharded", n=g.n, directed_edges=g.num_edges,
        one_chip_backend=one.backend, backend=four.backend,
        devices=jax.device_count(), lpa_iterations=four.lpa_iterations,
        split_iterations=four.split_iterations,
        communities=four.num_communities,
        smoke_fit_s_incl_compile=f"{t_fit:.1f}",
        smoke_timings_s=fit_timings(four), bit_identical_to_one_chip=same)
    check(four.backend == "sharded", f"ran {four.backend}")
    check(same, "sharded labels differ from the one-chip labels")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated graph")
    args = ap.parse_args()
    if args.chips == 1:
        # Hold one chip even on a multi-chip host; must precede JAX's start.
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX backend "
                         f"{backend!r}); this run needs the chip")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        for k in cache_events:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache_events[k] += 1
    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    dev = jax.devices()[0]
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=jax.device_count(), compile_cache=cache_dir)
    phases = ([("sharded", phase_sharded)] if args.chips == 4 else [
        ("graph500-22", lambda: phase_powerlaw(args.seed)),
        ("road-grid", phase_road),
        ("parity", lambda: phase_parity(args.seed)),
        ("serving", lambda: phase_serving(args.seed)),
    ])
    for name, run in phases:
        t0, c0 = time.perf_counter(), _COMPILE_S[0]
        run()
        say(name, ok=True, smoke_phase_s=f"{time.perf_counter() - t0:.1f}",
            smoke_compile_s=f"{_COMPILE_S[0] - c0:.1f}")
    say("compile-cache", dir=cache_dir, **cache_events)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
