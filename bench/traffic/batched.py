"""Batched traffic: one caller sends many small graphs through the batcher.

One ``Engine(EngineConfig())`` behind one ``MicroBatcher(engine,
max_batch, batch_timeout_ms)``.  The configuration's networks, made
during set-up, are served in passes, in their fixed order: the
caller submits the next ``min(in_flight, left in the pass)`` requests,
waits on all of them, and repeats until ``--seconds`` have passed; the
pass in flight then is finished and counted, so a window holds whole
passes: batches differ in rate (d 1,024 against 512), and a window cut
at a batch counted one batch more or less as the last one's end fell
about the deadline, which moved ``edges_per_s`` by ~3.5%.  With
``in_flight`` equal to ``max_batch`` each submission is one dispatch;
the last of a pass is shorter and the batcher sends it after its
linger.  Every request is cold.
Set-up serves one submission of each batch bucket (backend and packed
shapes) a pass reaches, which loads or compiles every program the window
runs.

End to end: ``edges_per_s``, the directed edges (``Graph.num_edges``) of
every request completed in the window over the time from the window's
start to the end of its last batch.

The window's information line gives, per dispatch, its size, bucket,
backend, tile width, the seconds of its submission (submit to the last
result) and its members' largest ``lpa_iterations`` and
``split_iterations``; and each network's iterations in the first pass,
with whether every later pass repeated them.

``records`` are the requests in the order served, each a ``Member``: the
network's place in the pass, its directed edges and its
``DetectionResult``.  The per-layer readers of this traffic group them
into dispatches where ``batch_index`` restarts at 0, and read the
program's spans inside ``window_bounds`` (``span_ms``).

Correct: every member's labels equal the plain reference's for its own
network, vertex for vertex; the reference runs once per network.
"""
from __future__ import annotations

import bisect
import itertools
import time
from typing import NamedTuple

from lpabench import graphs, reference
from lpabench.harness import Check, Window


class Member(NamedTuple):
    net: int          # the network's place in the pass
    edges: int        # its directed edges
    result: object    # its DetectionResult


def make_inputs(run) -> list:
    """The configuration's ``(n, edges, weights)`` networks, in the order
    they are served: one call of its generator, cached under the key
    ``oneshot`` gives a draw of one graph."""
    cfg = run.cell.config
    gen = run.generator(cfg["generator"])
    params = cfg["params"]
    return graphs.cached(
        cfg["name"], run.cell.name, run.seed, {**params, "graphs": 1},
        lambda: gen.generate(params, graphs.rng_for(run.seed)))


def submissions(count: int, in_flight: int) -> list:
    """The ``(lo, hi)`` request ranges of one pass, in order."""
    return [(lo, min(lo + in_flight, count))
            for lo in range(0, count, in_flight)]


def setup(run):
    import jax

    from repro.core.batch import GraphBatch
    from repro.core.graph import build_graph
    from repro.engine import Engine, EngineConfig
    from repro.engine.bucketing import batch_bucket_for
    from repro.engine.registry import choose_backend_batch
    from repro.launch.microbatch import MicroBatcher
    traffic = run.cell.traffic
    with run.span("bench.generate"):
        inputs = make_inputs(run)
        built = [build_graph(edges, weights, n=n)
                 for n, edges, weights in inputs]
        jax.block_until_ready([g.dst for g in built])
    engine = Engine(EngineConfig())
    cfg = engine.config
    batcher = MicroBatcher(engine, max_batch=int(traffic["max_batch"]),
                           batch_timeout_ms=float(
                               traffic["batch_timeout_ms"]))
    ranges = submissions(len(built), int(traffic["in_flight"]))
    warmed = set()
    with run.span("bench.warmup"):
        for lo, hi in ranges:
            members = built[lo:hi]
            key = (choose_backend_batch(members, cfg), batch_bucket_for(
                GraphBatch.pack(members), bucketing=cfg.bucketing,
                min_vertex_bucket=cfg.min_vertex_bucket,
                min_edge_bucket=cfg.min_edge_bucket))
            if key not in warmed:
                warmed.add(key)
                for s in [batcher.submit(g) for g in members]:
                    s.result()
    return {"graphs": built, "batcher": batcher, "inputs": inputs,
            "ranges": ranges}


def window(run, state) -> Window:
    batcher, built = state["batcher"], state["graphs"]
    ranges = state["ranges"]
    records, sent = [], []
    t0 = time.perf_counter()
    deadline = t0 + run.seconds
    while True:
        lo, hi = ranges[len(sent) % len(ranges)]
        start = time.perf_counter()
        with run.span("bench.batch"):
            subs = [batcher.submit(built[i]) for i in range(lo, hi)]
            results = [s.result() for s in subs]
        end = time.perf_counter()
        sent.append((hi - lo, end - start))
        records.extend(Member(i, built[i].num_edges, r)
                       for i, r in zip(range(lo, hi), results))
        if end >= deadline and len(sent) % len(ranges) == 0:
            break
    batcher.close()
    elapsed = end - t0
    return Window(
        end_to_end={"edges_per_s": sum(m.edges for m in records) / elapsed},
        attempted=len(records), records=records,
        info={"requests": len(records), "window_s": elapsed,
              "window_bounds": [t0, end], "networks": len(built),
              "passes": len(records) / len(built),
              "dispatches": dispatch_info(records, sent),
              **pass_iterations(records, len(built))})


def dispatches(records: list) -> list:
    """The window's requests grouped by the dispatch that served them."""
    groups = []
    for m in records:
        if m.result.batch_index == 0 or not groups:
            groups.append([])
        groups[-1].append(m)
    return groups


def dispatch_info(records: list, sent: list) -> list:
    """One entry per dispatch; ``seconds`` is the time of the submission
    its requests came in (``sent``: each submission's request count and
    seconds)."""
    ends = list(itertools.accumulate(count for count, _ in sent))
    out, pos = [], 0
    for group in dispatches(records):
        r = group[0].result
        out.append({"size": len(group), "bucket": list(r.bucket),
                    "backend": r.backend, "width": r.bucket[-1],
                    "seconds": sent[bisect.bisect_right(ends, pos)][1],
                    "lpa_max": max(m.result.lpa_iterations for m in group),
                    "split_max": max(m.result.split_iterations
                                     for m in group)})
        pos += len(group)
    return out


def pass_iterations(records: list, count: int) -> dict:
    """Each network's iterations in the first pass, and whether every
    later pass repeated them."""
    its = [(m.result.lpa_iterations, m.result.split_iterations)
           for m in records]
    first = its[:count]
    return {"lpa_iterations": [a for a, _ in first],
            "split_iterations": [b for _, b in first],
            "passes_agree": all(its[i] == first[i % count]
                                for i in range(len(its)))}


def span_ms(win: Window, *names: str) -> float | None:
    """Sum over ``names`` of the mean duration, in ms, of the program's
    spans of that name that started inside the window; None when one of
    them has none (a program without that span)."""
    from repro.obs import TRACER
    t0, t1 = win.info["window_bounds"]
    total = 0.0
    for name in names:
        durs = [s.dur for s in TRACER.spans(name)
                if s.name == name and t0 <= s.t0 <= t1]
        if not durs:
            return None
        total += 1e3 * sum(durs) / len(durs)
    return total


def check(run, state, win: Window):
    state.pop("graphs")
    state.pop("batcher")
    inputs = state["inputs"]
    expected = {i: reference.detect(*inputs[i])[0]
                for i in sorted({m.net for m in win.records})}
    wrong = [reference.mismatched_vertices(m.result.labels, expected[m.net])
             for m in win.records]
    return [Check("label_mismatch_vertices", sum(wrong), 0)], \
        sum(w > 0 for w in wrong)
