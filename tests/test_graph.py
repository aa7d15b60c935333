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


def test_padded_neighbors_roundtrip():
    g = random_graph(40, 5.0, seed=2, weighted=True)
    nbr, nw, nmask = to_padded_neighbors(g)
    assert nbr.shape[1] % 128 == 0
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
    assert nbr.shape[1] == 256 and int(nmask[0].sum()) == 200


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
