"""Graph500 Kronecker generator, vectorized.

Follows the Graph500 reference generator (the one LDBC Graphalytics used for
its ``graph500-*`` data sets): ``edge_factor * 2**scale`` edge samples, each
descending ``scale`` levels of the 2x2 initiator [[a, b], [c, 1-a-b-c]], then
a seeded permutation of the vertex ids so that id order carries no locality.
Self-loops are dropped and duplicates removed; weights are unit.

``generate(params, rng)`` returns ``(n, edges, weights)``: ``edges`` is an
(E, 2) int64 array of unique undirected pairs ``u < v``, ``weights`` None
(unit).
"""
from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, rng: np.random.Generator) -> np.ndarray:
    """Raw (edge_factor * 2**scale, 2) samples, before permutation."""
    n_samples = edge_factor << scale
    ab = a + b
    a_norm = a / ab
    c_norm = c / (1.0 - ab)
    src = np.zeros(n_samples, np.int64)
    dst = np.zeros(n_samples, np.int64)
    for bit in range(scale):
        ii = rng.random(n_samples, dtype=np.float32) > ab
        thresh = np.where(ii, np.float32(c_norm), np.float32(a_norm))
        jj = rng.random(n_samples, dtype=np.float32) > thresh
        src |= ii.astype(np.int64) << bit
        dst |= jj.astype(np.int64) << bit
    return np.stack([src, dst], axis=1)


def unique_undirected(n: int, edges: np.ndarray) -> np.ndarray:
    """Drop self-loops, orient ``u < v``, remove duplicates (sorted)."""
    u = np.minimum(edges[:, 0], edges[:, 1])
    v = np.maximum(edges[:, 0], edges[:, 1])
    keep = u != v
    key = np.unique(u[keep] * n + v[keep])
    return np.stack([key // n, key % n], axis=1)


def generate(params: dict, rng: np.random.Generator):
    scale = int(params["scale"])
    n = 1 << scale
    raw = kronecker_edges(scale, int(params["edge_factor"]),
                          float(params["a"]), float(params["b"]),
                          float(params["c"]), rng)
    if params.get("permute", True):
        raw = rng.permutation(n).astype(np.int64)[raw]
    return n, unique_undirected(n, raw), None
