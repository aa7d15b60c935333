"""Unified Engine API: backend parity, shape-bucketed compile cache,
warm starts, and legacy-wrapper compatibility."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import disconnected_fraction, gsl_lpa, gve_lpa
from repro.engine import (
    CompileCache,
    Engine,
    EngineConfig,
    backend_names,
    choose_backend,
)
from repro.graphgen import erdos_renyi, karate_club, planted_partition

BACKENDS = ("segment", "tile", "sharded")

GRAPHS = {
    "er": lambda: erdos_renyi(180, 5.0, seed=11),
    "planted": lambda: planted_partition(6, 30, 0.3, 0.01, seed=3)[0],
    "karate": lambda: karate_club()[0],
}


def fresh_engine(**kw):
    return Engine(EngineConfig(**kw), cache=CompileCache())


def test_backends_registered():
    assert set(BACKENDS) <= set(backend_names())


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_backend_label_parity(name):
    """segment, tile, and sharded (exchange_every=1) produce identical
    compacted labels on the same graph."""
    g = GRAPHS[name]()
    eng = fresh_engine()
    results = {be: eng.fit(g, backend=be) for be in BACKENDS}
    ref = results["segment"]
    for be in BACKENDS:
        assert np.array_equal(results[be].labels, ref.labels), (name, be)
        assert results[be].lpa_iterations == ref.lpa_iterations, (name, be)
        assert results[be].num_communities == ref.num_communities
        assert float(disconnected_fraction(
            g, jnp.asarray(results[be].labels))) == 0.0


@pytest.mark.parametrize("backend", BACKENDS)
def test_same_bucket_compiles_once(backend):
    """Two different graphs (different n, edges) in one shape bucket ->
    exactly one trace/compile per backend stage, and the second fit is a
    cache hit with a valid result.  Audited via the general trace-audit
    gate (tests/test_trace_audit.py runs the full-workload version)."""
    from repro.analysis import TraceAudit
    g1 = erdos_renyi(200, 5.0, seed=1)
    g2 = erdos_renyi(230, 5.0, seed=2)
    eng = fresh_engine(backend=backend)

    with TraceAudit() as audit:
        r1 = eng.fit(g1)
        r2 = eng.fit(g2)

    assert r1.bucket == r2.bucket
    assert not r1.cache_hit and r2.cache_hit
    audit.assert_no_excess()   # nothing traced twice, incl. the 2nd fit
    deltas = audit.deltas()
    assert {tag for tag, _ in deltas} == {f"{backend}:propagate",
                                          f"{backend}:split"}
    assert all(ctx == (backend, r1.bucket) for _, ctx in deltas)
    assert float(disconnected_fraction(g2, jnp.asarray(r2.labels))) == 0.0


def test_second_fit_bit_identical():
    g = erdos_renyi(150, 4.0, seed=9)
    eng = fresh_engine()
    r1 = eng.fit(g)
    r2 = eng.fit(g)
    assert r2.cache_hit
    assert np.array_equal(r1.labels, r2.labels)
    assert r1.lpa_iterations == r2.lpa_iterations


def test_legacy_wrappers_ride_the_engine():
    """gsl_lpa / gve_lpa are facades over the Engine (exact bucketing) and
    agree with a direct exact-bucket Engine fit."""
    g, _ = karate_club()
    eng = fresh_engine(bucketing="exact")
    res = eng.fit(g)
    legacy = gsl_lpa(g, split="lp")
    assert np.array_equal(legacy.labels, res.labels)
    assert legacy.lpa_iterations == res.lpa_iterations
    assert legacy.split_iterations == res.split_iterations
    assert legacy.lpa_seconds > 0 and legacy.split_seconds > 0
    none = gve_lpa(g)
    assert none.split_iterations == 0


@pytest.mark.parametrize("split", ["none", "lp", "lpp", "bfs_host"])
def test_split_methods_through_engine(split):
    g = erdos_renyi(120, 5.0, seed=6)
    res = fresh_engine(split=split).fit(g)
    assert res.labels.shape == (g.n,)
    assert res.labels.min() == 0
    if split != "none":
        assert float(disconnected_fraction(g, jnp.asarray(res.labels))) == 0.0


def test_warm_start_auto_keys_on_graph_fingerprint():
    """Regression: warm_start="auto" used to key on the vertex count
    alone, silently warm-starting from an *unrelated* graph of the same
    size.  It now keys on a structural fingerprint (n, m, offset/dst
    hashes)."""
    g1 = erdos_renyi(100, 4.0, seed=1)
    g2 = erdos_renyi(100, 4.0, seed=2)   # same n, different structure
    assert g1.n == g2.n
    eng = fresh_engine(warm_start="auto")
    r1 = eng.fit(g1)
    assert not r1.warm_started
    r2 = eng.fit(g2)
    assert not r2.warm_started, "warm-started from an unrelated graph"
    r3 = eng.fit(g2)
    assert r3.warm_started  # same structure -> warm start still applies


def test_fingerprint_precomputed_no_recompute_on_repeat_fits():
    """Regression: ``build_graph`` now fingerprints from the host-side
    CSR before device transfer, so warm_start="auto" fits (and
    StreamSession updates) never pay a device->host copy + CRC per fit.
    A lazy recompute inside fit would call zlib.crc32 — assert it
    doesn't."""
    from unittest import mock
    g = erdos_renyi(80, 4.0, seed=3)
    eng = fresh_engine(warm_start="auto")
    with mock.patch("zlib.crc32",
                    side_effect=AssertionError("fingerprint recomputed")):
        r1 = eng.fit(g)
        r2 = eng.fit(g)
    assert not r1.warm_started and r2.warm_started


def test_warm_start_auto_and_explicit():
    g, _ = planted_partition(8, 30, 0.3, 0.005, seed=5)
    eng = fresh_engine(warm_start="auto")
    r1 = eng.fit(g)
    assert not r1.warm_started
    r2 = eng.fit(g)  # previous labels re-used -> converges quickly
    assert r2.warm_started
    assert r2.lpa_iterations <= r1.lpa_iterations
    assert float(disconnected_fraction(g, jnp.asarray(r2.labels))) == 0.0

    cold = fresh_engine()
    r3 = cold.fit(g, init_labels=r1.labels)
    assert r3.warm_started
    assert float(disconnected_fraction(g, jnp.asarray(r3.labels))) == 0.0


def test_result_shape_and_metrics():
    g, _ = karate_club()
    res = fresh_engine(compute_metrics=True).fit(g)
    assert res.num_communities == len(set(res.labels.tolist()))
    assert set(res.timings) == {"prepare", "propagation", "split", "compact"}
    assert res.modularity is not None and res.modularity > 0.2
    assert res.disconnected_fraction == 0.0
    assert res.backend in BACKENDS


def test_auto_backend_selection_runs():
    g = erdos_renyi(64, 3.0, seed=2)
    cfg = EngineConfig(backend="auto")
    assert choose_backend(g, cfg) in BACKENDS
    res = Engine(cfg, cache=CompileCache()).fit(g)
    assert res.backend in BACKENDS


def test_config_validation():
    with pytest.raises(ValueError):
        EngineConfig(backend="gpu-magic")
    with pytest.raises(ValueError):
        EngineConfig(split="fancy")
    with pytest.raises(ValueError):
        EngineConfig(exchange_every=0)
    g = erdos_renyi(40, 3.0, seed=1)
    with pytest.raises(ValueError):
        fresh_engine(split="lpp").fit(g, backend="sharded")
    with pytest.raises(ValueError):
        fresh_engine().fit(g, init_labels=np.full(g.n, g.n + 3))


# --- fused sweeps (fuse_sweeps) ---------------------------------------------

@pytest.mark.parametrize("split", ["lp", "lpp", "none"])
def test_fused_fit_parity_across_splits(split):
    """fuse_sweeps on vs off: identical labels AND iteration counts.
    The lazy-wake restructure defers each sub-sweep's wake to the next
    dispatch, so the fused path is bit-neutral by construction."""
    g = GRAPHS["er"]()
    base = fresh_engine(backend="tile", split=split, kernel_mode="ref",
                        fuse_sweeps="off").fit(g)
    fused = fresh_engine(backend="tile", split=split, kernel_mode="ref",
                         fuse_sweeps="on").fit(g)
    assert np.array_equal(fused.labels, base.labels), split
    assert fused.lpa_iterations == base.lpa_iterations
    assert fused.split_iterations == base.split_iterations
    # cross-backend: the segment oracle agrees with the fused tile run
    seg = fresh_engine(backend="segment", split=split).fit(g)
    assert np.array_equal(fused.labels, seg.labels), split


def test_fused_fit_parity_interpret():
    """Interpret mode runs the real fused kernel body on CPU."""
    g = GRAPHS["karate"]()
    base = fresh_engine(backend="tile", kernel_mode="interpret",
                        fuse_sweeps="off").fit(g)
    fused = fresh_engine(backend="tile", kernel_mode="interpret",
                         fuse_sweeps="on").fit(g)
    assert np.array_equal(fused.labels, base.labels)
    assert fused.lpa_iterations == base.lpa_iterations
    assert fused.split_iterations == base.split_iterations


def test_fused_fit_many_parity():
    """Batched dispatch threads the carried wake state per graph."""
    graphs = [erdos_renyi(120, 4.0, seed=s) for s in (1, 2, 3)]
    base = fresh_engine(backend="tile", kernel_mode="ref",
                        fuse_sweeps="off").fit_many(graphs)
    fused = fresh_engine(backend="tile", kernel_mode="ref",
                         fuse_sweeps="on").fit_many(graphs)
    for b, f in zip(base, fused):
        assert np.array_equal(f.labels, b.labels)
        assert f.lpa_iterations == b.lpa_iterations
        assert f.split_iterations == b.split_iterations
        assert f.batch_size == b.batch_size == 3


def test_tile_admission_counts_bucket_cells():
    """Cells are counted at the compiled shapes: bucket rows times the
    lane-rounded degree bucket.  A 2048x2048 grid (degree 4, so 128-wide
    rows) is refused; a 1024x1024 grid is admitted."""
    from repro.engine.bucketing import vertex_degree_bucket
    from repro.engine.registry import tile_limit_error
    assert tile_limit_error(*vertex_degree_bucket(1 << 20, 4)) is None
    assert "cells" in tile_limit_error(*vertex_degree_bucket(1 << 22, 4))
    assert "degree bucket" in tile_limit_error(256, 2048)


def test_forced_tile_refuses_unadmitted_graph():
    """backend="tile" on a graph wider than the kernels' widest row raises
    a ValueError before anything is traced, solo and batched."""
    from repro.core.graph import build_graph
    from repro.engine import TRACE_LOG
    star = build_graph(np.stack([np.zeros(1500, np.int64),
                                 np.arange(1, 1501)], axis=1), n=1501)
    before = TRACE_LOG.snapshot()
    eng = fresh_engine(backend="tile")
    with pytest.raises(ValueError, match="tile backend refuses"):
        eng.fit(star)
    with pytest.raises(ValueError, match="tile backend refuses"):
        eng.fit_many([star, erdos_renyi(40, 3.0, seed=1)])
    assert TRACE_LOG.snapshot() == before
    # auto falls back to segment and still answers
    assert fresh_engine().fit(star).backend == "segment"


@pytest.mark.parametrize("backend", BACKENDS)
def test_edge_slots_are_the_padded_layout(backend):
    """edge_slots is the padded edge bucket on segment and the padded
    tiles' rows x d on tile and sharded, solo and batched."""
    import jax

    from repro.engine.backends.sharded import _shard_rows
    from repro.engine.bucketing import tile_rows
    rows = {"segment": None, "tile": tile_rows,
            "sharded": lambda n: _shard_rows(n, jax.device_count())}[backend]
    g = erdos_renyi(100, 4.0, seed=8)
    eng = fresh_engine(backend=backend)
    r = eng.fit(g)
    n, m, d = r.bucket
    assert r.edge_slots == (m if rows is None else rows(n) * d)
    assert 0 < g.num_edges <= r.edge_slots
    if backend != "sharded":
        members = eng.fit_many([g, karate_club()[0]])
        _, n_b, m_b, d_b = members[0].bucket
        want = m_b if rows is None else rows(n_b) * d_b
        assert [x.edge_slots for x in members] == [want, want]


def _sweep_programs(backend, fuse):
    """The compiled text of a backend's propagate and split programs."""
    from repro.core.graph import to_padded_neighbors
    from repro.engine.bucketing import bucket_for, pad_graph, tile_rows
    from repro.engine.registry import get_backend
    g = erdos_renyi(100, 4.0, seed=8)
    cfg = EngineConfig(fuse_sweeps=fuse)
    bucket = bucket_for(g)
    plan = get_backend(backend).build(bucket, cfg)
    n_real = jnp.int32(g.n)
    if backend == "segment":
        gp = pad_graph(g, bucket)
        labels = jnp.arange(gp.n, dtype=jnp.int32)
        ones = jnp.ones(gp.n, bool)
        return (plan.propagate.lower(gp, n_real, labels, ones),
                plan.split.lower(gp, labels, n_real))
    rows = tile_rows(bucket.n)
    nbr, nw, nmask = (jnp.asarray(x) for x in to_padded_neighbors(
        pad_graph(g, bucket), d_max=bucket.d))
    labels = jnp.arange(rows, dtype=jnp.int32)
    return (plan.propagate.lower(nbr, nw, nmask, n_real, labels,
                                 jnp.ones(rows, bool)),
            plan.split.lower(nbr, nmask, labels, labels, n_real))


@pytest.mark.parametrize("backend,fuse", [("segment", "auto"),
                                          ("tile", "off"), ("tile", "on")])
def test_sweep_programs_carry_named_scopes(backend, fuse):
    """Each sweep program names its gather, reduce and wake operations
    (and segment's propagation its sort) in the compiled program's
    metadata."""
    scopes = ("sweep.gather", "sweep.sort", "sweep.reduce", "sweep.wake")
    propagate, split = (lowered.compile().as_text()
                        for lowered in _sweep_programs(backend, fuse))
    for text, sort in ((propagate, backend == "segment"), (split, False)):
        found = {s for s in scopes if f"/{s}/" in text}
        assert found == set(scopes) - (set() if sort else {"sweep.sort"})
