"""Road-class graph: a seeded random spanning tree of a side x side lattice
plus seeded lattice edges up to a target average degree.

The tree is the minimum spanning tree of the lattice under seeded random
edge keys, so the graph is one component whatever the seed, every degree is
at most 4, and the diameter grows like a road network's, not like a
lattice's.  Lattice edges outside the tree are then added, drawn from the
seed, until ``directed edges / vertices`` reaches ``avg_degree`` (2.13 in
DIMACS10 ``asia_osm``).

``weights`` is ``"unit"`` (``asia_osm`` itself carries none) or
``"travel_time"``: integer travel times drawn uniformly from
``[weight_min, weight_max]`` and stored as float32, the integer-weight form
of DIMACS9 ``USA-road-t``.  Integers keep every float32 sum of a vertex's
(at most 4) weights exact, while bfloat16's 8 significant bits cannot hold
them, so those labels depend on the arithmetic precision.

``generate(params, rng)`` returns ``(n, edges, weights)``: unique undirected
pairs ``u < v`` and one float32 weight per pair, or None for unit weights.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import minimum_spanning_tree


def lattice_edges(side: int) -> np.ndarray:
    idx = np.arange(side * side, dtype=np.int64).reshape(side, side)
    return np.concatenate([
        np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], 1),
        np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], 1),
    ])


def generate(params: dict, rng: np.random.Generator):
    side = int(params["side"])
    n = side * side
    lattice = lattice_edges(side)
    # keys in [1, 2): a zero would read as "no edge" to scipy
    keys = 1.0 + rng.random(len(lattice))
    tree = minimum_spanning_tree(coo_matrix(
        (keys, (lattice[:, 0], lattice[:, 1])), shape=(n, n))).tocoo()
    tree_key = np.minimum(tree.row, tree.col).astype(np.int64) * n \
        + np.maximum(tree.row, tree.col)
    lattice_key = lattice[:, 0] * n + lattice[:, 1]
    spare = lattice_key[~np.isin(lattice_key, tree_key)]
    target = int(round(float(params["avg_degree"]) * n / 2))
    extra = rng.choice(spare, size=max(target - len(tree_key), 0),
                       replace=False)
    key = np.sort(np.concatenate([tree_key, extra]))
    edges = np.stack([key // n, key % n], axis=1)
    if params["weights"] == "unit":
        return n, edges, None
    weights = rng.integers(int(params["weight_min"]),
                           int(params["weight_max"]) + 1,
                           size=len(edges)).astype(np.float32)
    return n, edges, weights
