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

Correct: every fit's labels equal the plain reference's for its graph,
vertex for vertex.
"""
from __future__ import annotations

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
    fits, order = [], []
    t0 = time.perf_counter()
    deadline = t0 + run.seconds
    while True:
        i = len(fits) % len(built)
        with run.span("bench.fit"):
            fits.append(engine.fit(built[i]))
        order.append(i)
        t_end = time.perf_counter()
        if t_end >= deadline:
            break
    elapsed = t_end - t0
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
              "communities": [r.num_communities for r in fits]})


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
