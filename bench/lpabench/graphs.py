"""Inputs made from the seed, cached on disk under ``bench/.cache``.

A graph is ``(n, edges, weights)``: unique undirected pairs ``u < v`` and
float32 weights, or None for unit weights.  A list of them is stored in one
uncompressed ``.npz`` keyed by configuration, cell, seed and a hash of the
parameters that made it, so a repeated seed loads in about a second instead
of regenerating.
"""
from __future__ import annotations

import hashlib
import json
from pathlib import Path

import numpy as np

CACHE = Path(__file__).resolve().parents[1] / ".cache" / "graphs"


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (1 << 64))


def _key(config: str, cell: str, seed: int, params) -> Path:
    digest = hashlib.sha256(json.dumps(params, sort_keys=True).encode())
    return CACHE / config / f"{cell}-{int(seed)}-{digest.hexdigest()[:12]}.npz"


def save(path: Path, graphs: list) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    ns = np.array([g[0] for g in graphs], np.int64)
    counts = np.array([len(g[1]) for g in graphs], np.int64)
    weighted = np.array([g[2] is not None for g in graphs], bool)
    edges = np.concatenate([np.asarray(g[1], np.int32).reshape(-1, 2)
                            for g in graphs])
    weights = np.concatenate(
        [np.zeros(0, np.float32)]
        + [np.asarray(g[2], np.float32) for g in graphs if g[2] is not None])
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, n=ns, counts=counts, weighted=weighted, edges=edges,
             weights=weights)
    tmp.replace(path)


def load(path: Path) -> list:
    with np.load(path) as z:
        ns, counts, weighted = z["n"], z["counts"], z["weighted"]
        edges, weights = z["edges"], z["weights"]
    bounds = np.concatenate([[0], np.cumsum(counts)])
    wbounds = np.concatenate([[0], np.cumsum(counts * weighted)])
    return [(int(ns[i]), edges[bounds[i]:bounds[i + 1]].astype(np.int64),
             weights[wbounds[i]:wbounds[i + 1]] if weighted[i] else None)
            for i in range(len(ns))]


def cached(config: str, cell: str, seed: int, params, make) -> list:
    """``make()`` -> list of graphs, run once per key."""
    path = _key(config, cell, seed, params)
    if path.is_file():
        try:
            return load(path)
        except (OSError, ValueError, KeyError):
            path.unlink(missing_ok=True)
    graphs = make()
    save(path, graphs)
    return graphs


def degree_stats(n: int, edges: np.ndarray) -> tuple[int, int]:
    """(directed edges, maximum degree) of an undirected edge list."""
    deg = np.bincount(np.asarray(edges).ravel(), minlength=n)
    return 2 * len(edges), int(deg.max()) if n else 0
