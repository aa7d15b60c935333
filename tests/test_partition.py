"""Out-of-core partitioned detection: planning, budget, and bit-parity.

The acceptance contract this suite pins:
  * partitioned ``fit`` labels are **bit-identical** to in-core ``fit``
    for segment + tile across split modes (the sequential partition
    sweep against a shared snapshot reproduces every synchronous in-core
    sweep exactly);
  * halo sets exactly cover all cross-partition edges;
  * peak resident edge bytes never exceed the budget (ledger-asserted);
  * ``check_connected == 0`` still holds globally after the
    per-partition split + cross-partition unification.
"""

import numpy as np
import pytest

from conftest import random_graph
from repro.core.graph import build_graph
from repro.engine import CompileCache, Engine, EngineConfig
from repro.partition.ooc import (
    fit_out_of_core,
    in_core_edge_bytes,
    open_source,
)
from repro.partition.plan import (
    attach_halos,
    parse_bytes,
    plan_partitions,
)
from repro.partition.slices import (
    HaloLabelCache,
    InMemorySource,
    MemoryBudgetExceeded,
    MemoryLedger,
    SliceLoader,
    load_partition,
    slice_nbytes,
)

# Small enough that every (backend, split) combo compiles fast; sized so
# a tight budget forces a real multi-partition sweep with halos.
FIXTURES = {
    "random": lambda: random_graph(220, 4.0, seed=3),
    "communities": lambda: _planted(),
    # denser mix for the tile backend, whose (8, d_bucket)-cell dense-tile
    # floor per partition needs in-core bytes comfortably above it
    "tile_mix": lambda: random_graph(256, 10.0, seed=21),
}


def _planted():
    from repro.graphgen import planted_partition
    return planted_partition(8, 24, 0.3, 0.01, seed=4)[0]


def _row_ptr(graph):
    return np.asarray(graph.row_ptr)


def _tight_budget(graph, backend: str = "segment") -> int:
    """A budget well under the graph's in-core edge bytes, so the
    engine must partition (and the ledger has real work to bound).
    The tile backend's floor is one dense (8, d_bucket) tile."""
    from repro.partition.ooc import IN_CORE_EDGE_BYTES
    in_core = graph.m_pad * IN_CORE_EDGE_BYTES
    if backend == "tile":
        return max(in_core // 2, 20_000)
    return in_core // 3


# --- planning ---------------------------------------------------------------

def test_plan_covers_and_balances():
    g = random_graph(300, 5.0, seed=0)
    plan = plan_partitions(_row_ptr(g), num_partitions=7)
    assert plan.parts[0].lo == 0 and plan.parts[-1].hi == g.n
    for a, b in zip(plan.parts[:-1], plan.parts[1:]):
        assert a.hi == b.lo
    rp = _row_ptr(g)
    for p in plan.parts:
        assert p.e_lo == rp[p.lo] and p.e_hi == rp[p.hi]
    # degree balance: a window overshoots the ideal share by at most
    # one row's degree (rows are atomic)
    target = -(-plan.num_edges // plan.num_partitions)
    assert plan.max_part_edges <= target + int(np.max(rp[1:] - rp[:-1]))


def test_plan_by_max_edges_and_row_cap():
    g = random_graph(200, 6.0, seed=1)
    plan = plan_partitions(_row_ptr(g), max_edges=100)
    assert all(p.num_edges <= 100 + int(np.max(_row_ptr(g)[1:]
                                               - _row_ptr(g)[:-1]))
               for p in plan.parts)
    capped = plan_partitions(_row_ptr(g), max_edges=10 ** 9, max_vertices=16)
    assert all(p.size <= 16 for p in capped.parts)
    with pytest.raises(ValueError):
        plan_partitions(_row_ptr(g))
    with pytest.raises(ValueError):
        plan_partitions(_row_ptr(g), max_edges=10, num_partitions=3)


def test_halo_exactly_covers_cross_partition_edges():
    g = random_graph(150, 5.0, seed=2)
    src = np.asarray(g.src)[: g.num_edges]
    dst = np.asarray(g.dst)[: g.num_edges]
    plan = attach_halos(plan_partitions(_row_ptr(g), num_partitions=5),
                        lambda lo, hi: dst[lo:hi])
    for p in plan.parts:
        in_part = (src >= p.lo) & (src < p.hi)
        crossing = dst[in_part & ((dst < p.lo) | (dst >= p.hi))]
        assert set(p.halo.tolist()) == set(crossing.tolist())
        # sorted, unique, and disjoint from the owned range
        assert np.all(np.diff(p.halo) > 0)
        assert not np.any((p.halo >= p.lo) & (p.halo < p.hi))


def test_parse_bytes():
    assert parse_bytes(4096) == 4096
    assert parse_bytes("64MB") == 64_000_000
    assert parse_bytes("1GiB") == 1 << 30
    assert parse_bytes("1Gi") == 1 << 30   # common binary-unit spelling
    assert parse_bytes("2.5KB") == 2500
    for bad in ("sixty MB", "64XB", "1i"):
        with pytest.raises(ValueError):
            parse_bytes(bad)


# --- slices + ledger --------------------------------------------------------

def test_load_partition_reconstructs_global_edges():
    g = random_graph(120, 4.0, seed=5)
    src = np.asarray(g.src)[: g.num_edges]
    dst = np.asarray(g.dst)[: g.num_edges]
    source = InMemorySource(g)
    plan = attach_halos(plan_partitions(_row_ptr(g), num_partitions=4),
                        lambda lo, hi: source.window("dst", lo, hi))
    for p in plan.parts:
        res = load_partition(source, p)
        # local ids map back to exactly the window's global edges
        gsrc = res.local_ids[res.src]
        gdst = res.local_ids[res.dst]
        assert np.array_equal(gsrc, src[p.e_lo:p.e_hi])
        assert np.array_equal(gdst, dst[p.e_lo:p.e_hi])
        # local row_ptr spans the window
        assert res.row_ptr[0] == 0 and res.row_ptr[-1] == p.num_edges


def test_ledger_budget_is_hard():
    ledger = MemoryLedger(1000)
    ledger.acquire(800, "a")
    with pytest.raises(MemoryBudgetExceeded):
        ledger.acquire(300, "b")
    ledger.release(800)
    assert ledger.current == 0 and ledger.peak == 800


def test_loader_lru_stays_under_budget():
    g = random_graph(200, 5.0, seed=6)
    source = InMemorySource(g)
    plan = attach_halos(plan_partitions(_row_ptr(g), num_partitions=6),
                        lambda lo, hi: source.window("dst", lo, hi))
    from repro.partition.slices import slice_nbytes
    budget = max(slice_nbytes(p) for p in plan.parts) * 2
    ledger = MemoryLedger(budget)
    loader = SliceLoader(source, plan, ledger)
    for sweep in range(3):
        for i in range(plan.num_partitions):
            loader.load(i)
    assert ledger.peak <= budget
    assert loader.loads > plan.num_partitions  # tight budget => reloads
    loader.clear()
    assert ledger.current == 0


def test_loader_prefetch_stages_under_budget():
    """Round-robin sweeps with the next window staged: the ledger's
    high-water mark (current + staged reservation) stays <= budget, and
    staged windows are adopted instead of re-read."""
    g = random_graph(200, 5.0, seed=6)
    source = InMemorySource(g)
    plan = attach_halos(plan_partitions(_row_ptr(g), num_partitions=6),
                        lambda lo, hi: source.window("dst", lo, hi))
    budget = max(slice_nbytes(p) for p in plan.parts) * 2
    ledger = MemoryLedger(budget)
    loader = SliceLoader(source, plan, ledger, prefetch=True)
    for _sweep in range(2):
        for i in range(plan.num_partitions):
            loader.load(i)
            loader.prefetch((i + 1) % plan.num_partitions, keep=i)
    assert ledger.peak <= budget
    assert loader.prefetches > 0 and loader.prefetch_hits > 0
    loader.clear()                      # joins + releases staged windows
    assert ledger.current == 0


def test_halo_label_cache_epoch_invalidation():
    """A cached view is served byte-free while its rows are unchanged;
    after an owning partition rewrites a vertex (advance), only the
    stale rows are re-uploaded."""
    ledger = MemoryLedger(1 << 20)
    arr = (np.arange(100, dtype=np.int32) * 10).copy()
    cache = HaloLabelCache(ledger, n=100, n_loc=16, what="labels")
    ids = np.array([5, 7, 50, 99])
    v1 = np.asarray(cache.gather(0, ids, arr))
    assert np.array_equal(v1[:4], arr[ids]) and v1.shape == (16,)
    assert cache.hits == 0 and cache.bytes == 4 * arr.itemsize
    # unchanged revisit: a pure hit, zero bytes uploaded
    v2 = np.asarray(cache.gather(0, ids, arr))
    assert cache.hits == 1 and np.array_equal(v2, v1)
    assert cache.bytes == 4 * arr.itemsize
    # the owner of vertex 50 relabels it: exactly that entry refreshes
    arr[50] = -1
    changed = np.zeros(100, dtype=bool)
    changed[50] = True
    cache.advance(changed)
    v3 = np.asarray(cache.gather(0, ids, arr))
    assert v3[2] == -1
    assert np.array_equal(v3[[0, 1, 3]], v1[[0, 1, 3]])
    assert cache.hits == 1              # a refresh visit is not a hit
    assert cache.bytes == 5 * arr.itemsize          # 4 initial + 1 stale
    assert cache.bytes_saved == (4 + 3) * arr.itemsize
    cache.drop()
    assert ledger.current == 0


def test_halo_label_cache_respects_budget():
    """No room for even one entry -> gather declines (returns None) and
    the caller falls back to the plain host gather; spill frees LRU."""
    arr = np.arange(32, dtype=np.int32)
    tiny = HaloLabelCache(MemoryLedger(32), n=32, n_loc=16)  # entry = 64 B
    assert tiny.gather(0, np.array([1, 2]), arr) is None
    ledger = MemoryLedger(160)          # room for two 64 B entries
    cache = HaloLabelCache(ledger, n=32, n_loc=16)
    for idx in range(3):                # third insert evicts LRU entry 0
        assert cache.gather(idx, np.array([idx]), arr) is not None
    assert cache.stats()["entries"] == 2 and ledger.peak <= 160
    assert cache.spill(64) == 64        # window loads can reclaim room
    assert cache.stats()["entries"] == 1


def test_single_partition_too_big_raises():
    g = random_graph(100, 5.0, seed=7)
    source = InMemorySource(g)
    with pytest.raises(MemoryBudgetExceeded):
        fit_out_of_core(source, EngineConfig(backend="segment"),
                        memory_budget=64, num_partitions=2)


# --- bit-parity with the in-core engine ------------------------------------

@pytest.mark.parametrize("backend,fixtures", [
    ("segment", ("random", "communities")),
    ("tile", ("tile_mix",)),
])
@pytest.mark.parametrize("split", ["lp", "lpp", "none"])
def test_ooc_parity_backends_splits(backend, fixtures, split):
    cfg = EngineConfig(backend=backend, split=split)
    eng = Engine(cfg, cache=CompileCache())
    for name in fixtures:
        g = FIXTURES[name]()
        budget = _tight_budget(g, backend)
        ref = eng.fit(g)
        ooc = eng.fit(g, memory_budget=budget)
        assert ooc.partitions > 1, f"{name}: budget did not partition"
        assert np.array_equal(ref.labels, ooc.labels), \
            f"{name}: {backend}/{split} OOC labels diverge from in-core"
        assert ref.lpa_iterations == ooc.lpa_iterations
        assert ref.split_iterations == ooc.split_iterations
        assert ooc.ooc["peak_resident_bytes"] <= budget
        if split != "none":
            assert ooc.check_connected(g) == 0.0


def test_ooc_parity_shortcut_exact_weighted():
    g = random_graph(180, 4.0, seed=8)
    # beyond-paper shortcut: applied as a global pointer jump per sweep
    eng = Engine(EngineConfig(backend="segment", split="lpp",
                              shortcut=True), cache=CompileCache())
    assert np.array_equal(eng.fit(g).labels,
                          eng.fit(g, memory_budget=_tight_budget(g)).labels)
    # exact bucketing bakes the threshold with Python float semantics
    eng = Engine(EngineConfig(backend="segment", bucketing="exact"),
                 cache=CompileCache())
    assert np.array_equal(eng.fit(g).labels,
                          eng.fit(g, memory_budget=_tight_budget(g)).labels)
    # float32-exact weights keep the segment sums bit-stable
    rng = np.random.default_rng(9)
    e = rng.integers(0, 150, size=(400, 2))
    gw = build_graph(e, rng.choice([0.5, 1.0, 1.5, 2.0], size=400), n=150)
    eng = Engine(EngineConfig(backend="segment"), cache=CompileCache())
    assert np.array_equal(eng.fit(gw).labels,
                          eng.fit(gw, memory_budget=_tight_budget(gw)).labels)


def test_ooc_warm_start_parity():
    g = random_graph(200, 4.0, seed=10)
    eng = Engine(EngineConfig(backend="segment"), cache=CompileCache())
    base = eng.fit(g).labels
    frontier = np.zeros(g.n, bool)
    frontier[:40] = True
    ref = eng.fit(g, init_labels=base, init_active=frontier)
    ooc = eng.fit(g, init_labels=base, init_active=frontier,
                  memory_budget=_tight_budget(g))
    assert ref.warm_started and ooc.warm_started
    assert ooc.partitions > 1
    assert np.array_equal(ref.labels, ooc.labels)
    with pytest.raises(ValueError, match="init_labels"):
        eng.fit(g, init_labels=base[:-1], memory_budget=_tight_budget(g))


@pytest.mark.parametrize("split", ["lp", "lpp", "none"])
def test_ooc_segment_fused_parity(split):
    """Segment fused partition sweeps (one jitted dispatch per visit)
    are bit-identical to the unfused wake+move/wake+min pair."""
    g = random_graph(220, 4.0, seed=3)
    source = InMemorySource(g)
    budget = _tight_budget(g)
    runs = {}
    for fuse in ("on", "off"):
        cfg = EngineConfig(backend="segment", split=split, fuse_sweeps=fuse)
        runs[fuse] = fit_out_of_core(source, cfg, memory_budget=budget,
                                     cache=CompileCache())
    assert runs["on"].fused and not runs["off"].fused
    assert runs["on"].num_partitions > 1
    assert np.array_equal(runs["on"].labels, runs["off"].labels), split
    assert runs["on"].lpa_iterations == runs["off"].lpa_iterations
    assert runs["on"].split_iterations == runs["off"].split_iterations


def test_ooc_tile_fused_interpret_parity():
    """Tile fused partition sweeps under interpret mode (the real kernel
    body) against the in-core fit."""
    g = FIXTURES["tile_mix"]()
    eng = Engine(EngineConfig(backend="tile", kernel_mode="interpret",
                              fuse_sweeps="on"), cache=CompileCache())
    ref = eng.fit(g)
    ooc = eng.fit(g, memory_budget=_tight_budget(g, "tile"))
    assert ooc.partitions > 1
    assert np.array_equal(ref.labels, ooc.labels)
    assert ref.lpa_iterations == ooc.lpa_iterations
    assert ref.split_iterations == ooc.split_iterations


def test_ooc_prefetch_parity_and_budget():
    """Prefetch on vs off: same labels, same iteration counts, ledger
    peak (with the second window staged) still <= budget."""
    g = random_graph(220, 4.0, seed=3)
    source = InMemorySource(g)
    cfg = EngineConfig(backend="segment", split="lp")
    budget = _tight_budget(g)
    cache = CompileCache()
    base = fit_out_of_core(source, cfg, memory_budget=budget, cache=cache,
                           prefetch=False, halo_cache=False)
    # under this tight budget a second window cannot be reserved, so the
    # loader declines every stage — the run must still be exact
    pre = fit_out_of_core(source, cfg, memory_budget=budget, cache=cache,
                          prefetch=True, halo_cache=True)
    assert pre.num_partitions > 1
    assert np.array_equal(base.labels, pre.labels)
    assert base.lpa_iterations == pre.lpa_iterations
    assert base.split_iterations == pre.split_iterations
    assert pre.peak_resident_bytes <= budget
    assert base.peak_resident_bytes <= budget


def test_ooc_prefetch_and_halo_cache_engage():
    """With headroom over the windows, staged loads are adopted and the
    halo label cache serves revisits without re-gathering."""
    g = random_graph(220, 4.0, seed=3)
    source = InMemorySource(g)
    cfg = EngineConfig(backend="segment", split="lp")
    budget = 3 * in_core_edge_bytes(source)   # room for ~2 windows + caches
    run = fit_out_of_core(source, cfg, memory_budget=budget,
                          num_partitions=4, cache=CompileCache(),
                          prefetch=True, halo_cache=True)
    assert run.num_partitions == 4
    assert run.prefetches > 0 and run.prefetch_hits > 0
    assert run.halo_cache_hits > 0 and run.halo_cache_bytes_saved > 0
    assert run.peak_resident_bytes <= budget


# --- engine routing + guards -----------------------------------------------

def test_engine_routes_by_budget():
    g = random_graph(200, 4.0, seed=11)
    eng = Engine(EngineConfig(backend="segment"), cache=CompileCache())
    small = eng.fit(g, memory_budget=_tight_budget(g))
    assert small.partitions > 1 and small.ooc is not None
    big = eng.fit(g, memory_budget="1GB")
    assert big.partitions == 1 and big.ooc is None
    assert np.array_equal(small.labels, big.labels)
    # config-level budget applies without the per-call kwarg
    eng2 = Engine(EngineConfig(backend="segment",
                               memory_budget=_tight_budget(g)),
                  cache=CompileCache())
    assert eng2.fit(g).partitions > 1


def test_ooc_guards():
    g = random_graph(120, 4.0, seed=12)
    budget = _tight_budget(g)
    eng = Engine(EngineConfig(backend="segment", split="bfs_host"),
                 cache=CompileCache())
    with pytest.raises(ValueError, match="bfs_host"):
        eng.fit(g, memory_budget=budget)
    eng = Engine(EngineConfig(backend="segment", compute_metrics=True),
                 cache=CompileCache())
    with pytest.raises(ValueError, match="compute_metrics"):
        eng.fit(g, memory_budget=budget)
    eng = Engine(EngineConfig(backend="sharded"), cache=CompileCache())
    with pytest.raises(ValueError, match="partition"):
        eng.fit(g, memory_budget=budget)
    with pytest.raises(ValueError):
        EngineConfig(patch_churn_threshold=1.5)
    assert EngineConfig(memory_budget="64MB").memory_budget == 64_000_000


def test_ooc_sweeps_share_compiled_plans():
    """Every partition (and every later same-shape fit) reuses one
    executable per sweep stage — the compile cache keys on config, jax's
    jit cache on the uniform partition shapes."""
    from repro.engine.cache import TRACE_LOG
    g = random_graph(200, 4.0, seed=13)
    eng = Engine(EngineConfig(backend="segment"), cache=CompileCache())
    TRACE_LOG.reset()
    first = eng.fit(g, memory_budget=_tight_budget(g))
    traces = TRACE_LOG.total("segment:part_")
    assert first.partitions > 1
    eng.fit(g, memory_budget=_tight_budget(g))
    assert TRACE_LOG.total("segment:part_") == traces, \
        "second OOC fit re-traced the partition sweeps"


# --- store-backed path ------------------------------------------------------

def test_ooc_from_store_path(tmp_path, monkeypatch):
    from repro.io.formats import write_snap
    monkeypatch.setenv("REPRO_GRAPH_CACHE", str(tmp_path / "cache"))
    rng = np.random.default_rng(14)
    e = rng.integers(0, 300, size=(800, 2))
    path = tmp_path / "g.snap.txt"
    write_snap(path, e)

    eng = Engine(EngineConfig(backend="segment"), cache=CompileCache())
    ref = eng.fit(str(path))
    ooc = eng.fit(str(path), memory_budget="12KB")
    assert ooc.partitions > 1
    assert np.array_equal(ref.labels, ooc.labels)
    assert ooc.ooc["peak_resident_bytes"] <= parse_bytes("12KB")

    # the routing check for paths reads store metadata, not the arrays
    source = open_source(str(path))
    assert source.n == ref.labels.shape[0]
    assert in_core_edge_bytes(source) > parse_bytes("12KB")


def test_store_entry_windows_are_zero_copy(tmp_path, monkeypatch):
    from repro.io.formats import write_snap
    from repro.io.store import load_graph, open_graph
    monkeypatch.setenv("REPRO_GRAPH_CACHE", str(tmp_path / "cache"))
    rng = np.random.default_rng(15)
    e = rng.integers(0, 100, size=(250, 2))
    path = tmp_path / "g.snap.txt"
    write_snap(path, e)
    g = load_graph(str(path))
    handle = open_graph(str(path))
    assert handle.n == g.n and handle.num_edges == g.num_edges
    full_dst = np.asarray(g.dst)
    win = handle.window("dst", 10, 60)
    assert np.array_equal(win, full_dst[10:60])
    # zero-copy: the window is a view over the entry's mmap
    assert win.base is not None
    assert handle.fingerprint is not None


def test_ingest_cli_ooc(tmp_path, monkeypatch, capsys):
    from repro.io.formats import write_snap
    from repro.launch.ingest import main
    monkeypatch.setenv("REPRO_GRAPH_CACHE", str(tmp_path / "cache"))
    rng = np.random.default_rng(16)
    e = rng.integers(0, 200, size=(500, 2))
    path = tmp_path / "g.snap.txt"
    write_snap(path, e)
    out_json = tmp_path / "report.json"
    assert main([str(path), "--ooc", "--memory-budget", "16KB",
                 "--backend", "segment", "--cache-dir",
                 str(tmp_path / "cache"), "--json", str(out_json)]) == 0
    text = capsys.readouterr().out
    assert "ooc[segment]" in text and "partitions=" in text
    import json
    rep = json.loads(out_json.read_text())[0]
    assert rep["ooc"]["partitions"] > 1
    assert rep["ooc"]["peak_resident_bytes"] <= parse_bytes("16KB")
