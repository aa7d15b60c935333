"""The ego-network deployment: its generator, the batched path it drives,
and the readers of the batched traffic's per-layer metrics."""
from __future__ import annotations

import json
from types import SimpleNamespace

import numpy as np
import pytest
from tiny_cells import BENCH

from lpabench import bytemodel, graphs, peaks, reference, spec, tracing

CONFIG = json.loads((BENCH / "configs" / "ego-twitter.json").read_text())
PARAMS = CONFIG["params"]
SMALL = {**PARAMS, "egos": 12, "vertices": 480}
SEEDS = [0, 7, 2**31 + 5, 2**40 + 3]
GEN = spec.generator(BENCH, "ego")


def make(params, seed):
    return GEN.generate(params, graphs.rng_for(seed))


@pytest.fixture(scope="module")
def seed0():
    return make(PARAMS, 0)


def test_sizes_and_order_are_the_same_for_every_seed():
    """The data set is one fixed collection: sizes, order and edges come
    from the configuration's constants, the same for every seed."""
    a, b = make(SMALL, 1), make(SMALL, 2**40 + 3)
    assert [n for n, _, _ in a] == [n for n, _, _ in b] \
        == list(GEN.sizes(SMALL))
    assert all(np.array_equal(x, y) for (_, x, _), (_, y, _) in zip(a, b))
    other = make({**SMALL, "topology": SMALL["topology"] + 1}, 1)
    assert any(not np.array_equal(x, y)
               for (_, x, _), (_, y, _) in zip(a, other))


@pytest.mark.parametrize("seed", SEEDS)
def test_deterministic_in_seed(seed):
    first, again = make(SMALL, seed), make(SMALL, seed)
    for (n1, e1, w1), (n2, e2, w2) in zip(first, again):
        assert n1 == n2 and np.array_equal(e1, e2)
        assert w1 is None and w2 is None


def test_published_vertex_total_and_size_law():
    n = GEN.sizes(PARAMS)
    assert len(n) == CONFIG["published"]["egos"] == 973
    assert abs(int(n.sum()) - CONFIG["published"]["vertices"]) <= 10
    assert n.min() >= 4 and n.max() <= 1024   # every hub fits a tile row


def test_published_edge_total_at_seed_0(seed0):
    """The published edges are directed follows; the engine counts each
    undirected pair twice (``Graph.num_edges``)."""
    directed = 2 * sum(len(e) for _, e, _ in seed0)
    assert abs(directed / CONFIG["published"]["edges"] - 1) <= 0.01


def test_published_circle_total():
    count = sum(int(GEN.circles(n, PARAMS["alters_per_circle"]).max()) + 1
                for n in GEN.sizes(PARAMS))
    assert count == CONFIG["published"]["circles"] == 4869


def test_published_clustering(seed0):
    """Average local clustering coefficient, every vertex counted (0 below
    degree 2), within 0.01 of the published 0.5653."""
    local = []
    for n, e, _ in seed0:
        a = np.zeros((n, n), np.float32)
        a[e[:, 0], e[:, 1]] = a[e[:, 1], e[:, 0]] = 1
        deg = a.sum(1)
        tri = ((a @ a) * a).sum(1) / 2
        local.append(np.where(deg > 1, tri / np.maximum(deg * (deg - 1) / 2,
                                                        1), 0))
    got = float(np.concatenate(local).mean())
    assert abs(got - CONFIG["published"]["clustering"]) <= 0.01


def test_ego_is_adjacent_to_every_alter(seed0):
    for n, e, _ in seed0:
        hub = e[e[:, 0] == 0, 1]
        assert np.array_equal(np.sort(hub), np.arange(1, n))


@pytest.mark.parametrize("seed", SEEDS)
def test_edges_are_unique_pairs(seed):
    for n, e, _ in make(SMALL, seed):
        assert (e[:, 0] < e[:, 1]).all() and e.max() < n
        assert len(np.unique(e[:, 0] * n + e[:, 1])) == len(e)


def test_circles_split_the_alters_evenly():
    c = GEN.circles(100, 16.7)
    assert len(c) == 99 and c.max() + 1 == 6
    counts = np.bincount(c)
    assert counts.max() - counts.min() <= 1


def test_equal_sized_networks_differ(seed0):
    """Alters are shuffled across circles, so two networks of one size do
    not share an answer by construction."""
    by_size = {}
    for n, e, _ in seed0:
        by_size.setdefault(n, []).append(e)
    pairs = [(x, y) for nets in by_size.values() if len(nets) > 1
             for x, y in zip(nets, nets[1:])]
    assert len(pairs) > 100
    assert all(not np.array_equal(x, y) for x, y in pairs)


# --- the comparison that decides ``correct``, on the cell's networks ---

@pytest.fixture(scope="module")
def answered(seed0):
    """Two passes of the cell's networks, each member answered with the
    reference's labels, grouped into dispatches of 128."""
    traffic = spec.traffic_module(_cell())
    members = []
    for _ in range(2):
        for i, (n, e, w) in enumerate(seed0):
            members.append(traffic.Member(i, 2 * len(e), SimpleNamespace(
                labels=reference.detect(n, e, w)[0], batch_index=i % 128)))
    return traffic, seed0, members


def _faulty(members, fault):
    """The members' answers with ``fault`` planted in every dispatch."""
    out = list(members)
    for lo in range(0, len(out), 128):
        group = range(lo, min(lo + 128, len(out)))
        if fault == "constant":
            for i in group:
                out[i] = out[i]._replace(result=SimpleNamespace(
                    labels=np.zeros_like(out[i].result.labels),
                    batch_index=out[i].result.batch_index))
        elif fault == "member_swap":   # equal-sized members trade answers
            first = {}
            for i in group:
                size = len(out[i].result.labels)
                if size in first:
                    j = first.pop(size)
                    out[i], out[j] = (out[i]._replace(result=out[j].result),
                                      out[j]._replace(result=out[i].result))
                else:
                    first[size] = i
    return out


@pytest.mark.parametrize("fault", [None, "member_swap", "constant"])
def test_planted_faults_read_not_correct(answered, fault):
    """The cell's limit-0 comparison over every member: the reference's
    own answers read 0; equal-sized members trading answers, or every
    member answering one community, read not correct on most networks."""
    traffic, nets, members = answered
    win = SimpleNamespace(records=members if fault is None
                          else _faulty(members, fault))
    state = {"graphs": None, "batcher": None, "inputs": nets}
    (chk,), wrong_members = traffic.check(None, state, win)
    assert chk.name == "label_mismatch_vertices" and chk.limit == 0
    if fault is None:
        assert chk.value == 0 and wrong_members == 0
        return
    changed = sum(a.result is not b.result
                  for a, b in zip(members, win.records))
    assert chk.value > chk.limit
    assert wrong_members >= 0.8 * (changed if fault == "member_swap"
                                   else len(members))


# --- the batched path on ego networks ---

@pytest.mark.parametrize("backend", ["segment", "tile"])
def test_batcher_members_equal_reference_and_solo_fits(backend):
    """MicroBatcher -> Engine.fit_many on ego networks whose members stop at
    different iterations: each member's labels equal the bench reference's
    and a solo ``Engine.fit``'s."""
    from repro.core.graph import build_graph
    from repro.engine import CompileCache, Engine, EngineConfig
    from repro.launch.microbatch import MicroBatcher
    nets = make(SMALL, 3)
    built = [build_graph(e, w, n=n) for n, e, w in nets]
    engine = Engine(EngineConfig(backend=backend), cache=CompileCache())
    with MicroBatcher(engine, max_batch=6, batch_timeout_ms=50,
                      autostart=False) as mb:
        subs = [mb.submit(g) for g in built]
        mb.start()
        results = [s.result(timeout=600) for s in subs]
    assert mb.batch_sizes == [6, 6]
    assert len({r.lpa_iterations for r in results}) > 1
    assert {r.backend for r in results} == {backend}
    for (n, e, w), g, r in zip(nets, built, results):
        assert np.array_equal(r.labels, reference.detect(n, e, w)[0])
        solo = engine.fit(g)
        assert np.array_equal(r.labels, solo.labels)
        assert (r.lpa_iterations, r.split_iterations) == \
            (solo.lpa_iterations, solo.split_iterations)


class _Batcher:
    """Answers each request at once; batch_index restarts every dispatch
    of ``size``."""

    def __init__(self, size):
        self.size, self.sent = size, 0

    def submit(self, graph):
        index, self.sent = self.sent % self.size, self.sent + 1
        res = SimpleNamespace(batch_index=index, lpa_iterations=3,
                              split_iterations=2, bucket=(8, 64, 256, 8),
                              backend="tile")
        return SimpleNamespace(result=lambda: res)

    def close(self):
        pass


@pytest.mark.parametrize("networks,in_flight", [(7, 3), (6, 3), (2, 4)])
def test_window_ends_on_a_whole_pass(networks, in_flight):
    """A deadline that falls inside a pass finishes the pass: every
    window holds whole passes, whatever batch the deadline falls in."""
    import contextlib
    traffic = spec.traffic_module(_cell())
    ranges = traffic.submissions(networks, in_flight)
    state = {"batcher": _Batcher(in_flight), "ranges": ranges,
             "graphs": [SimpleNamespace(num_edges=10 + i)
                        for i in range(networks)]}
    run = SimpleNamespace(seconds=0.0,
                          span=lambda name: contextlib.nullcontext())
    win = traffic.window(run, state)
    assert win.info["passes"] == 1.0 and win.attempted == networks
    assert len(win.info["dispatches"]) == len(ranges)
    assert win.end_to_end["edges_per_s"] == pytest.approx(
        sum(10 + i for i in range(networks)) / win.info["window_s"])


# --- readers of the batched traffic ---

def _cell():
    return spec.load_cell("ego-twitter.batched")


def _member(net, edges, index, lpa, split, slots):
    return SimpleNamespace(net=net, edges=edges, result=SimpleNamespace(
        batch_index=index, lpa_iterations=lpa, split_iterations=split,
        edge_slots=slots))


@pytest.fixture
def two_dispatches():
    """A window of two dispatches: three members over 1,000 slots, then
    two over 500."""
    records = [_member(0, 100, 0, 3, 2, 1000), _member(1, 60, 1, 5, 2, 1000),
               _member(2, 40, 2, 2, 1, 1000), _member(3, 30, 0, 4, 1, 500),
               _member(4, 70, 1, 4, 3, 500)]
    return SimpleNamespace(records=records, info={"window_bounds": [0, 1]})


def test_slot_fill_counts_each_dispatch_once(two_dispatches):
    run = SimpleNamespace(cell=_cell())
    reader = spec.metric_reader(BENCH, "slot_fill.batched")
    assert reader.read(run, two_dispatches, None) == pytest.approx(
        100.0 * 300 / 1500)
    assert reader.read(run, SimpleNamespace(records=[]), None) is None


def test_ride_along_is_the_share_of_sweeps_after_convergence(
        two_dispatches):
    run = SimpleNamespace(cell=_cell())
    reader = spec.metric_reader(BENCH, "ride_along.batched")
    # dispatch 1 rides 3 x (5 + 2) = 21 and needs 5 + 7 + 3 = 15;
    # dispatch 2 rides 2 x (4 + 3) = 14 and needs 5 + 7 = 12
    assert reader.read(run, two_dispatches, None) == pytest.approx(
        100.0 * (1 - 27 / 35))
    same = SimpleNamespace(records=[_member(0, 1, 0, 3, 2, 8),
                                    _member(1, 1, 1, 3, 2, 8)])
    assert reader.read(run, same, None) == 0.0


def test_span_readers_read_the_window_spans(monkeypatch, two_dispatches):
    from repro.obs import TRACER, Span
    spans = [Span("engine.prepare", t0=0.2, dur=0.004),
             Span("engine.prepare", t0=0.6, dur=0.002),
             Span("engine.prepare", t0=1.5, dur=9.0),      # after the window
             Span("batch.collect", t0=0.1, dur=0.001),
             Span("batch.settle", t0=0.3, dur=0.003)]
    monkeypatch.setattr(TRACER, "spans", lambda prefix="": [
        s for s in spans if s.name.startswith(prefix)])
    run = SimpleNamespace(cell=_cell())
    read = {name: spec.metric_reader(BENCH, name).read(run, two_dispatches,
                                                       None)
            for name in ("prepare_ms.batched", "batcher_ms.batched",
                         "members_ms.batched")}
    assert read["prepare_ms.batched"] == pytest.approx(3.0)
    assert read["batcher_ms.batched"] == pytest.approx(4.0)
    assert read["members_ms.batched"] is None     # a program without it


# Fused kernel op texts recorded on a TPU v5e in a traced run of
# ego-twitter.batched (outputs are (rows, 1) columns, operands (rows, d)
# tiles; the (8, d, d) equality cube lives inside the kernel and never
# shows in the op's text).
RECORDED = {
    (16384, 1024): (
        "%fused_move.10 = (s32[16384,1]{1,0:T(8,128)S(1)}, "
        "s32[16384,1]{1,0:T(8,128)}) custom-call(s32[1,1]{1,0:T(1,128)} "
        "%bitcast.34, s32[16384,1024]{1,0:T(8,128)} %reshape.182, "
        "f32[16384,1024]{1,0:T(8,128)} %get-tuple-element.312, "
        "s32[16384,1024]{1,0:T(8,128)} %convert_element_type.81, "
        "s32[16384,1024]{1,0:T(8,128)} %reshape.183, "
        "s32[16384,1]{1,0:T(8,128)S(1)} %copy.24, "
        "s32[16384,1]{1,0:T(8,128)S(1)} %copy.25, "
        "s32[16384,1]{1,0:T(8,128)} %copy.26, "
        "s32[16384,1]{1,0:T(8,128)S(1)} %custom-call.21, "
        "s32[16384,1]{1,0:T(8,128)S(1)} %copy.29), "
        'custom_call_target="tpu_custom_call"'),
    (8192, 512): (
        "%fused_move.10 = (s32[8192,1]{1,0:T(8,128)S(1)}, "
        "s32[8192,1]{1,0:T(8,128)S(1)}) custom-call(s32[1,1]{1,0:T(1,128)} "
        "%bitcast.34, s32[8192,512]{1,0:T(8,128)S(1)} %custom-call.20, "
        "f32[8192,512]{1,0:T(8,128)S(1)} %custom-call.23, "
        "s32[8192,512]{1,0:T(8,128)S(1)} %convert_element_type.81, "
        "s32[8192,512]{1,0:T(8,128)S(1)} %reshape.183, "
        "s32[8192,1]{1,0:T(8,128)S(1)} %copy.24, "
        'custom_call_target="tpu_custom_call"'),
    (16384, 512): (
        "%fused_split.4 = s32[16384,1]{1,0:T(8,128)S(1)} custom-call("
        "s32[16384,512]{1,0:T(8,128)S(1)} %custom-call.8, "
        "s32[16384,512]{1,0:T(8,128)S(1)} %reshape.52, "
        "s32[16384,512]{1,0:T(8,128)S(1)} %convert_element_type.9, "
        "s32[16384,1]{1,0:T(8,128)} %copy.7, s32[16384,1]{1,0:T(8,128)} "
        '%copy.8), custom_call_target="tpu_custom_call"'),
}


@pytest.mark.parametrize("shape", list(RECORDED), ids=str)
def test_batched_kernel_calls_parse_as_their_tiles(shape):
    assert tracing.tile_shape(RECORDED[shape]) == shape


def test_kernel_roofline_batched_reads_the_recorded_calls():
    events = [tracing.Event("/device:TPU:0", "XLA Ops", text, 0.0, 5e6)
              for text in RECORDED.values()]
    summary = SimpleNamespace(ops=events)
    run = SimpleNamespace(cell=_cell(), peaks=peaks.peaks_for("TPU v5 lite"))
    want = 0.0
    for (rows, d), text in RECORDED.items():
        model = (bytemodel.fused_move_call if "fused_move" in text
                 else bytemodel.fused_split_call)
        nbytes, flops = model(rows, d)
        want += max(nbytes / 819e9, flops / 197e12)
    got = spec.metric_reader(BENCH, "kernel_roofline.road").read(
        run, None, summary)
    assert got == pytest.approx(100.0 * want / 15e-3)
    assert got < 100.0
