"""Modularity (paper Eq. 1)."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import modularity
from repro.core.graph import build_graph
from repro.core.modularity import modularity_host
from repro.graphgen import karate_club, ring_of_cliques
from conftest import random_graph


def test_ring_of_cliques_known_value():
    """k cliques in a ring, one-community-per-clique: Q = 1 - in_frac - ...
    Computed directly from Eq. 1 terms."""
    k, s = 8, 6
    g = ring_of_cliques(k, s)
    comm = jnp.asarray(np.repeat(np.arange(k), s).astype(np.int32))
    q = float(modularity(g, comm))
    m = s * (s - 1) / 2 * k + k          # undirected edge count
    in_c = s * (s - 1) / 2               # within one clique
    k_c = 2 * in_c + 2                   # degrees in one community
    expect = k * (in_c / m - (k_c / (2 * m)) ** 2)
    assert q == pytest.approx(expect, abs=1e-6)


def test_karate_known_split():
    g, faction = karate_club()
    q = float(modularity(g, jnp.asarray(faction)))
    # the 2-faction split scores ~0.358-0.372 depending on the exact
    # assignment of the boundary vertices (literature range)
    assert 0.35 <= q <= 0.38, q


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 40), st.integers(0, 10_000), st.integers(1, 5))
def test_bounds_and_invariance(n, seed, n_comm):
    g = random_graph(n, 4.0, seed=seed, weighted=True)
    rng = np.random.default_rng(seed)
    # modularity's contract: community labels are vertex ids in [0, n)
    comm = rng.integers(0, min(n_comm, n), size=n).astype(np.int32)
    q = float(modularity(g, jnp.asarray(comm)))
    assert -0.5 - 1e-6 <= q <= 1.0 + 1e-6
    assert q == pytest.approx(modularity_host(g, comm), abs=1e-5)
    # invariant under community relabeling within [0, n)
    perm = rng.permutation(n).astype(np.int32)
    q2 = float(modularity(g, jnp.asarray(perm[comm])))
    assert q == pytest.approx(q2, abs=1e-5)


@pytest.mark.parametrize("split", [False, True], ids=["one", "two"])
def test_float32_sums_match_float64_host(split):
    """A heavy edge beside thousands of light ones: summing community
    degrees one term after another in float32 drops the light terms
    (off by ~1e-4 here); pairwise sums stay at float32 rounding."""
    n = 3001
    edges = np.array([(0, 1), (1, 2)] + [(i, i + 1) for i in range(2, n - 1)])
    w = np.array([2.0 ** 20] + [1 / 16] * (len(edges) - 1))
    g = build_graph(edges, w, n=n)
    comm = (np.arange(n) >= n // 2) if split else np.zeros(n)
    comm = comm.astype(np.int32)
    q = float(modularity(g, jnp.asarray(comm)))
    assert q == pytest.approx(modularity_host(g, comm), abs=1e-5)


def test_single_community_zero():
    g = random_graph(30, 4.0, seed=3)
    q = float(modularity(g, jnp.zeros(30, jnp.int32)))
    assert q == pytest.approx(0.0, abs=1e-6)
