"""One-shot traffic: one caller fits large graphs, back to back.

Closed loop with a single caller: ``Engine(EngineConfig()).fit(graph)``,
cold every time (``warm_start`` is off by default), until ``--seconds`` have
passed; the fit in flight then is finished and counted.  The traffic's
``graphs`` (1 unless the workload says otherwise) graphs of the
configuration are made from the seed during set-up and fitted in turn, the
first again after the last.  Every graph of a configuration has the same
sizes, but the seed decides how many sweeps a fit needs; a set of several
graphs averages that out of a run.  Set-up fits one graph of each compiled
shape, which loads or compiles every program the window runs.

End to end: ``edges_per_s``, the directed edges of every fit completed in
the window over the time from the window's start to the end of its last
fit.  Everything inside ``Engine.fit`` is inside the window: padding,
transfers, the sweeps and compaction.

The window's information line also gives each fit's seconds, the median
and the longest fit, the longest gap between fits (``fit_times``), the
engine's stage timings of the longest fit and each stage's median over
the fits (``stage_medians``), so that a host stall or a slower host can
be told from a seed that needs more sweeps, and placed in a stage.

Correct: every fit's labels equal the plain reference's for its graph,
vertex for vertex.
"""
from __future__ import annotations

import statistics
import time

from lpabench import graphs, reference
from lpabench.harness import Check, Window


def make_inputs(run) -> list:
    """The cell's ``(n, edges, weights)`` graphs, all from the seed."""
    cfg = run.cell.config
    gen = run.generator(cfg["generator"])
    params = cfg["params"]
    count = int(run.cell.traffic.get("graphs", 1))

    def make():
        rng = graphs.rng_for(run.seed)
        return [gen.generate(params, rng) for _ in range(count)]
    return graphs.cached(cfg["name"], run.cell.name, run.seed,
                         {**params, "graphs": count}, make)


def setup(run):
    import jax

    from repro.core.graph import build_graph
    from repro.engine import Engine, EngineConfig
    from repro.engine.bucketing import bucket_for
    from repro.engine.registry import choose_backend
    with run.span("bench.generate"):
        inputs = make_inputs(run)
        built = [build_graph(edges, weights, n=n)
                 for n, edges, weights in inputs]
        jax.block_until_ready([g.dst for g in built])
    engine = Engine(EngineConfig())
    cfg = engine.config
    shapes = {}
    for g in built:
        shapes.setdefault((choose_backend(g, cfg), bucket_for(
            g, bucketing=cfg.bucketing,
            min_vertex_bucket=cfg.min_vertex_bucket,
            min_edge_bucket=cfg.min_edge_bucket)), g)
    with run.span("bench.warmup"):
        for g in shapes.values():
            engine.fit(g)
    return {"graphs": built, "engine": engine, "inputs": inputs}


def window(run, state) -> Window:
    engine, built = state["engine"], state["graphs"]
    fits, order, starts, ends = [], [], [], []
    t0 = time.perf_counter()
    deadline = t0 + run.seconds
    while True:
        i = len(fits) % len(built)
        starts.append(time.perf_counter())
        with run.span("bench.fit"):
            fits.append(engine.fit(built[i]))
        order.append(i)
        ends.append(time.perf_counter())
        if ends[-1] >= deadline:
            break
    elapsed = ends[-1] - t0
    longest = max(range(len(fits)), key=lambda k: ends[k] - starts[k])
    fit_n = [built[i].n for i in order]
    fit_edges = [built[i].num_edges for i in order]
    return Window(
        end_to_end={"edges_per_s": sum(fit_edges) / elapsed},
        attempted=len(fits), records=fits,
        info={"fits": len(fits), "window_s": elapsed, "graphs": len(built),
              "graph_of_fit": order, "fit_n": fit_n, "fit_edges": fit_edges,
              "backend": fits[0].backend, "bucket": list(fits[0].bucket),
              "lpa_iterations": [r.lpa_iterations for r in fits],
              "split_iterations": [r.split_iterations for r in fits],
              "communities": [r.num_communities for r in fits],
              **fit_times(t0, starts, ends),
              "fit_max_timings": fits[longest].timings,
              "stage_s_median": stage_medians(fits)})


def fit_times(t0: float, starts: list, ends: list) -> dict:
    """Each fit's time and the host's time between fits, so that a stall
    (a fit or a gap far above the median) shows in a run's output.  The
    fits and the gaps, the first from the window's start, add up to
    ``window_s``."""
    fit_s = [e - s for s, e in zip(starts, ends)]
    gaps = [s - e for s, e in zip(starts, [t0] + ends[:-1])]
    return {"fit_s_median": statistics.median(fit_s),
            "fit_s_max": max(fit_s), "fit_gap_s_max": max(gaps),
            "fit_s": fit_s}


def stage_medians(fits: list) -> dict:
    """The median over the window's fits of each engine stage's seconds:
    ``prepare`` and ``compact`` are host work, ``propagation`` and
    ``split`` wait on the device, so a slower host shows apart from a
    seed that needs more sweeps."""
    stages = sorted(set().union(*(r.timings for r in fits)))
    return {k: statistics.median(r.timings.get(k, 0.0) for r in fits)
            for k in stages}


def check(run, state, win: Window):
    state.pop("graphs")
    state.pop("engine")
    inputs = state["inputs"]
    expected = {i: reference.detect(*inputs[i])[0]
                for i in sorted(set(win.info["graph_of_fit"]))}
    wrong = [reference.mismatched_vertices(r.labels, expected[i])
             for i, r in zip(win.info["graph_of_fit"], win.records)]
    return [Check("label_mismatch_vertices", sum(wrong), 0)], \
        sum(w > 0 for w in wrong)
