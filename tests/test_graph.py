"""Graph representation invariants."""
import numpy as np
import pytest
pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.graph import build_graph, to_numpy_adj, to_padded_neighbors
from conftest import random_graph


def test_symmetrize_and_dedup():
    g = build_graph(np.array([[0, 1], [1, 0], [0, 1], [2, 2]]), n=3)
    # (0,1) x3 merged into weight 3 each direction; self loop dropped
    assert g.num_edges == 2
    adj = to_numpy_adj(g)
    assert adj[0] == [(1, 3.0)]
    assert adj[1] == [(0, 3.0)]
    assert adj[2] == []


def test_csr_consistency():
    g = random_graph(50, 6.0, seed=1)
    row_ptr = np.asarray(g.row_ptr)
    src = np.asarray(g.src)[: g.num_edges]
    # src array must be the CSR expansion of row_ptr
    expect = np.repeat(np.arange(g.n), row_ptr[1:] - row_ptr[:-1])
    assert np.array_equal(src, expect)
    # padding is masked
    assert not np.asarray(g.edge_mask)[g.num_edges:].any()
    assert np.asarray(g.wgt)[g.num_edges:].sum() == 0


def test_weighted_degree():
    e = np.array([[0, 1], [1, 2]])
    w = np.array([2.0, 5.0], np.float32)
    g = build_graph(e, w, n=3)
    np.testing.assert_allclose(np.asarray(g.kdeg), [2.0, 7.0, 5.0])
    assert float(g.total_weight) == pytest.approx(14.0)  # 2m


def _star_with_leaf_path(degree: int, seed: int):
    """A hub of ``degree`` weighted leaves, the leaves joined in a path
    (leaf degree <= 3), so the maximum degree is exactly ``degree``."""
    leaves = np.arange(1, degree + 1)
    edges = [np.stack([np.zeros(degree, np.int64), leaves], 1)]
    if degree >= 3:
        edges.append(np.stack([leaves[:-1], leaves[1:]], 1))
    e = np.concatenate(edges)
    w = np.random.default_rng(seed).uniform(0.5, 4.0, len(e))
    return build_graph(e, w.astype(np.float32), n=degree + 1)


# maximum degree -> tile row width: the next power of two, at least 8,
# below 128; lane-rounded (a multiple of 128) from there up
TILE_WIDTHS = {1: 8, 4: 8, 9: 16, 100: 128, 129: 256, 700: 1024}


@pytest.mark.parametrize("degree", sorted(TILE_WIDTHS))
def test_padded_neighbors_roundtrip(degree):
    g = _star_with_leaf_path(degree, seed=2)
    nbr, nw, nmask = to_padded_neighbors(g)
    assert nbr.shape == (-(-g.n // 8) * 8, TILE_WIDTHS[degree])
    adj = to_numpy_adj(g)
    for i in range(g.n):
        got = sorted((int(nbr[i, j]), float(nw[i, j]))
                     for j in range(nbr.shape[1]) if nmask[i, j])
        want = sorted((v, w) for v, w in adj[i])
        assert got == want
    # padding slots are weight-0 self edges
    self_rows = np.arange(nbr.shape[0])[:, None]
    assert ((nbr == self_rows) | nmask).all()


def test_padded_neighbors_refuses_truncation():
    """A d_max below the maximum degree raises instead of dropping edges."""
    star = build_graph(np.stack([np.zeros(200, np.int64),
                                 np.arange(1, 201)], axis=1), n=201)
    with pytest.raises(ValueError, match="maximum degree 200"):
        to_padded_neighbors(star, d_max=128)
    nbr, _nw, nmask = to_padded_neighbors(star, d_max=200)
    assert nbr.shape[1] == 200 and int(nmask[0].sum()) == 200


@pytest.mark.parametrize("degree", sorted(TILE_WIDTHS))
@pytest.mark.parametrize("bucketing", ["pow2", "exact"])
def test_ooc_partition_width_is_the_in_core_width(degree, bucketing):
    """Out-of-core tile partitions are as wide as the in-core bucket, so
    both reduce over the same widths and their float sums agree."""
    from repro.engine.bucketing import bucket_for
    from repro.partition.ooc import _shapes_for
    from repro.partition.plan import plan_partitions
    g = _star_with_leaf_path(degree, seed=3)
    plan = plan_partitions(np.asarray(g.row_ptr), num_partitions=2)
    assert _shapes_for(plan, bucketing).d \
        == bucket_for(g, bucketing=bucketing).d


def test_degree4_lattice_tile_labels_equal_segment():
    """A road-like lattice (degree <= 4, 8-wide tile rows) gets the
    segment backend's labels from the tile backend."""
    from repro.engine import CompileCache, Engine, EngineConfig
    from repro.graphgen import grid2d
    g = grid2d(24)
    fits = {be: Engine(EngineConfig(backend=be), cache=CompileCache()).fit(g)
            for be in ("segment", "tile")}
    n_bucket, _m, d = fits["tile"].bucket
    assert d == 8 and fits["tile"].edge_slots == n_bucket * 8
    assert np.array_equal(fits["tile"].labels, fits["segment"].labels)
    assert fits["tile"].lpa_iterations == fits["segment"].lpa_iterations


def _lane_rounded_limit_error(n, d_real, bucketing):
    """The tile admission as it was with 128-lane tile rows."""
    from repro.engine.bucketing import next_pow2, tile_rows
    from repro.engine.registry import _TILE_MAX_CELLS
    from repro.kernels.tiling import MAX_TILE_DEGREE
    d_real = max(d_real, 1)
    if bucketing == "exact":
        rows, d = n, -(-d_real // 128) * 128
    else:
        rows, d = next_pow2(n, 256), -(-next_pow2(d_real) // 128) * 128
    if d > MAX_TILE_DEGREE:
        return "degree"
    return "cells" if tile_rows(rows) * d > _TILE_MAX_CELLS else None


@pytest.mark.parametrize("bucketing", ["pow2", "exact"])
def test_tile_admission_unchanged_by_narrow_rows(monkeypatch, bucketing):
    """Admission still counts 128 lanes a row, so no graph changes
    backend: a 2^22-row degree-4 graph stays on segment, 2^20 rows on
    tile, and every (n, degree) is refused or admitted as before."""
    from repro.engine import EngineConfig, registry
    from repro.engine.bucketing import vertex_degree_bucket
    monkeypatch.setattr(registry.jax, "default_backend", lambda: "tpu")
    cfg = EngineConfig(bucketing=bucketing)
    assert registry._tile_or_segment(1 << 22, 4, cfg) == "segment"
    assert registry._tile_or_segment(1 << 20, 4, cfg) == "tile"
    for n in (1, 1000, 1 << 17, (1 << 17) + 1, 1 << 19, 1 << 20,
              (1 << 20) + 8, 1 << 21, 1 << 22):
        for d_real in (0, 1, 4, 8, 9, 64, 65, 127, 128, 129, 300, 513,
                       1024, 1025, 25_000):
            why = registry.tile_limit_error(*vertex_degree_bucket(
                n, d_real, bucketing=bucketing))
            old = _lane_rounded_limit_error(n, d_real, bucketing)
            assert (why is None) == (old is None), (n, d_real)
            if old is not None:
                assert ("cells" in why) == (old == "cells"), (n, d_real)
            assert registry._tile_or_segment(n, d_real, cfg) \
                == ("tile" if old is None else "segment"), (n, d_real)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 60), st.integers(0, 10_000))
def test_build_graph_properties(n, seed):
    g = random_graph(n, 4.0, seed=seed)
    src = np.asarray(g.src)[: g.num_edges]
    dst = np.asarray(g.dst)[: g.num_edges]
    wgt = np.asarray(g.wgt)[: g.num_edges]
    # no self loops
    assert (src != dst).all()
    # symmetry with equal weights
    fwd = {(int(s), int(d)): float(w) for s, d, w in zip(src, dst, wgt)}
    for (s, d), w in fwd.items():
        assert fwd.get((d, s)) == w
