"""The generators are deterministic in the seed and make what they claim."""
from __future__ import annotations

import numpy as np
import pytest
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components
from tiny_cells import BENCH

from lpabench import graphs, spec

KRON = {"scale": 10, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
        "permute": True}
ROAD = {"side": 64, "avg_degree": 2.13, "weights": "travel_time",
        "weight_min": 100,
        "weight_max": 10000}
SEEDS = [0, 7, 2**31 + 5, 2**40 + 3]


def make(family, params, seed):
    return spec.generator(BENCH, family).generate(params,
                                                  graphs.rng_for(seed))


@pytest.mark.parametrize("family,params", [("kronecker", KRON),
                                           ("road", ROAD)])
@pytest.mark.parametrize("seed", SEEDS)
def test_deterministic_in_seed(family, params, seed):
    n1, e1, w1 = make(family, params, seed)
    n2, e2, w2 = make(family, params, seed)
    assert n1 == n2 and np.array_equal(e1, e2)
    assert (w1 is None and w2 is None) or np.array_equal(w1, w2)
    _, e3, _ = make(family, params, seed + 1)
    assert not np.array_equal(e1, e3)


@pytest.mark.parametrize("seed", SEEDS)
def test_edges_are_unique_pairs(seed):
    n, e, w = make("kronecker", KRON, seed)
    assert w is None and n == 1 << KRON["scale"]
    assert (e[:, 0] < e[:, 1]).all() and e.max() < n
    assert len(np.unique(e[:, 0] * n + e[:, 1])) == len(e)


@pytest.mark.parametrize("seed", SEEDS)
def test_road_is_one_component_at_degree_2_13(seed):
    n, e, w = make("road", ROAD, seed)
    assert abs(2 * len(e) / n - 2.13) <= 0.01
    deg = np.bincount(e.ravel(), minlength=n)
    assert deg.max() <= 4
    adj = coo_matrix((np.ones(len(e)), (e[:, 0], e[:, 1])), shape=(n, n))
    assert connected_components(adj, directed=False)[0] == 1
    assert w.dtype == np.float32 and (w == np.round(w)).all()
    assert w.min() >= ROAD["weight_min"] and w.max() <= ROAD["weight_max"]


def test_cache_round_trip(tmp_path, monkeypatch):
    monkeypatch.setattr(graphs, "CACHE", tmp_path)
    calls = []

    def build():
        calls.append(1)
        return [make("road", ROAD, 3), make("kronecker", KRON, 3)]
    first = graphs.cached("c", "cell", 3, ROAD, build)
    again = graphs.cached("c", "cell", 3, ROAD, build)
    assert len(calls) == 1
    for (n1, e1, w1), (n2, e2, w2) in zip(first, again):
        assert n1 == n2 and np.array_equal(e1, e2)
        assert (w1 is None) == (w2 is None)


def test_road_unit_weights_share_the_topology():
    _, e_tt, w_tt = make("road", ROAD, 9)
    _, e_unit, w_unit = make("road", {**ROAD, "weights": "unit"}, 9)
    assert w_unit is None and w_tt is not None
    assert np.array_equal(e_tt, e_unit)
