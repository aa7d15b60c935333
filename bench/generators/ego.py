"""Ego networks: one hub per network, adjacent to all of its alters, and
circles of alters joined densely inside and sparsely between.

SNAP ``ego-Twitter`` publishes totals only (973 egos, 81,306 vertices,
1,768,149 directed edges, 4,869 circles, average clustering 0.5653), so
the per-network law is assumed and fixed by the configuration.  The data
set is one fixed collection, so every draw comes from the configuration's
constants and none from the seed:

* sizes: network ``i`` of ``egos`` has ``n_i`` vertices, the ego
  included: the lognormal quantile ``exp(sigma * Phi^-1((i + 1/2) /
  egos))``, scaled and rounded so that the sizes sum to ``vertices``;
* order: the networks come in one permutation drawn from the constant
  ``order``, so every seed has the same sizes in the same places;
* topology: vertex 0 is the ego, adjacent to every alter; the alters
  ``1 .. n_i - 1`` split into ``max(1, round((n_i - 1) /
  alters_per_circle))`` circles of near-equal size, the alters' ids
  shuffled across them (so equal-sized networks differ); a pair of
  alters joins with probability ``p_in`` inside a circle and ``p_out``
  between circles, drawn from the constant ``topology``.

``generate(params, rng)`` returns the ``egos`` networks in that order, each
``(n, edges, None)``: unique undirected pairs ``u < v``, unit weights.  It
draws nothing from ``rng``: every seed gets the same networks.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _rounded(scale: float, quantiles: np.ndarray) -> int:
    return int(np.round(scale * quantiles).sum())


def sizes(params: dict) -> np.ndarray:
    """The networks' vertex counts, in the order they are served."""
    egos, total = int(params["egos"]), int(params["vertices"])
    inv = NormalDist().inv_cdf
    quantiles = np.exp(float(params["sigma"]) * np.array(
        [inv((i + 0.5) / egos) for i in range(egos)]))
    # the smallest scale whose rounded sizes reach the total
    lo, hi = 0.0, 2.0 * total / quantiles.sum()
    for _ in range(100):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if _rounded(mid, quantiles) < total else (lo, mid)
    n = np.round(hi * quantiles).astype(np.int64)
    return n[np.random.default_rng(int(params["order"])).permutation(egos)]


def circles(n: int, alters_per_circle: float) -> np.ndarray:
    """The circle of each alter ``1 .. n - 1``: consecutive blocks of
    near-equal size."""
    alters = n - 1
    count = max(1, int(round(alters / alters_per_circle)))
    return np.arange(alters, dtype=np.int64) * count // max(alters, 1)


def network(n: int, params: dict, rng: np.random.Generator) -> np.ndarray:
    """One ego network's undirected edges ``u < v``, sorted."""
    circle = circles(n, float(params["alters_per_circle"]))[
        rng.permutation(n - 1)]
    a, b = np.triu_indices(n - 1, k=1)
    p = np.where(circle[a] == circle[b], float(params["p_in"]),
                 float(params["p_out"]))
    keep = rng.random(len(a)) < p
    hub = np.stack([np.zeros(n - 1, np.int64), np.arange(1, n)], axis=1)
    alters = np.stack([a[keep] + 1, b[keep] + 1], axis=1)
    return np.concatenate([hub, alters]).astype(np.int64)


def generate(params: dict, rng: np.random.Generator) -> list:
    if params["weights"] != "unit":
        raise ValueError(f"ego networks are unweighted, got weights "
                         f"{params['weights']!r}")
    draws = np.random.default_rng(int(params["topology"]))
    return [(int(n), network(int(n), params, draws), None)
            for n in sizes(params)]
