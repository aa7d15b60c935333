"""Plain reference of GSL-LPA: label propagation, Split-Last, compaction.

Written from the algorithm's description with numpy and scipy only; it
imports nothing of the system under test and reads none of its arrays.
It takes the undirected edge list the benchmark generated.

Semantics (the paper's Algorithm 3 as this system specifies it):

* Labels start as vertex ids; every vertex starts unprocessed (active).
* Vertices fall into two parity classes by ``hash(id, -1) & 1``.  Each
  iteration sweeps the class-0 vertices, then the class-1 vertices; the
  second sub-sweep sees the first's labels.
* A sweep over the active vertices of one class: for each such vertex ``u``
  and each label ``c`` among its neighbors, ``W[u, c]`` is the summed weight
  of the edges to neighbors labelled ``c``.  The best label has the largest
  ``W``; ties go to the largest ``hash(c, 2 * iteration + sweep)``, then to
  the smallest ``c``.  ``u`` adopts it only if its ``W`` is strictly above
  ``max(W[u, label(u)], 0)``.  Swept vertices go to sleep; the neighbors of
  every vertex that changed wake up.
* Propagation stops once an iteration changes at most
  ``int(float32(tau) * float32(n))`` labels, or after ``max_iterations``.
* Split-Last: each (community, connected component within the community)
  becomes its own community, named by its smallest vertex id.  Here that is
  a connected-components pass over the same-community edges (Algorithm 2's
  result), not the system's minimum-label propagation.
* Compaction: communities are renumbered 0..K-1 in the order of their
  names.

``dtype`` is the precision the per-label weight sums are formed and compared
in: the configuration's float32, or a lower one for the control.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

TAU = 0.05
MAX_ITERATIONS = 20


def label_hash(labels: np.ndarray, seed: int) -> np.ndarray:
    """Per-sweep label priority: a Knuth multiplicative mix in uint32."""
    x = np.asarray(labels).astype(np.int64).astype(np.uint32)
    x = x * np.uint32(2654435761)
    s = np.array([seed], np.int64).astype(np.uint32)
    x = x ^ (s * np.uint32(0x9E3779B9))
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    return (x & np.uint32(0x7FFFFFFF)).astype(np.int64)


class DirectedCsr:
    """Both directions of each undirected edge, sorted by source."""

    def __init__(self, n: int, edges: np.ndarray, weights=None):
        edges = np.asarray(edges, np.int64).reshape(-1, 2)
        w = (np.ones(len(edges), np.float32) if weights is None
             else np.asarray(weights, np.float32))
        src = np.concatenate([edges[:, 0], edges[:, 1]])
        dst = np.concatenate([edges[:, 1], edges[:, 0]])
        order = np.lexsort((dst, src))
        self.n = int(n)
        self.src, self.dst, self.w = src[order], dst[order], \
            np.concatenate([w, w])[order]


def _sweep(g: DirectedCsr, labels, cand, seed, dtype):
    """One sub-sweep over the vertices in ``cand``; returns new labels."""
    sel = cand[g.src]
    u, c = g.src[sel], labels[g.dst[sel]]
    if len(u) == 0:
        return labels
    w = g.w[sel].astype(dtype)
    key = u * g.n + c
    order = np.argsort(key, kind="stable")
    key, w = key[order], w[order]
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    run_w = np.add.reduceat(w, starts)                    # W[u, c] in dtype
    run_u, run_c = key[starts] // g.n, key[starts] % g.n
    ustarts = np.flatnonzero(np.r_[True, run_u[1:] != run_u[:-1]])
    uu = run_u[ustarts]
    per_u = np.repeat(np.arange(len(ustarts)), np.diff(np.r_[ustarts,
                                                             len(run_u)]))
    best_w = np.maximum.reduceat(run_w, ustarts)
    is_best = run_w >= best_w[per_u]
    h = np.where(is_best, label_hash(run_c, seed), -1)
    best_h = np.maximum.reduceat(h, ustarts)
    pick = is_best & (h == best_h[per_u])
    best_c = np.minimum.reduceat(np.where(pick, run_c, g.n), ustarts)
    zero = np.zeros((), dtype)
    cur_w = np.maximum.reduceat(
        np.where(run_c == labels[run_u], run_w, zero), ustarts)
    adopt = (best_w > zero) & (best_w > cur_w)
    new = labels.copy()
    new[uu[adopt]] = best_c[adopt]
    return new


def propagate(g: DirectedCsr, dtype=np.float32, tau: float = TAU,
              max_iterations: int = MAX_ITERATIONS):
    """Label propagation to convergence; returns (labels, iterations)."""
    n = g.n
    labels = np.arange(n, dtype=np.int64)
    active = np.ones(n, bool)
    parity = (label_hash(np.arange(n), -1) & 1).astype(bool)
    threshold = int(np.float32(tau) * np.float32(n))
    it, delta = 0, n
    while delta > threshold and it < max_iterations:
        delta = 0
        for sweep, klass in enumerate((~parity, parity)):
            cand = active & klass
            new = _sweep(g, labels, cand, 2 * it + sweep, dtype)
            changed = new != labels
            wake = np.zeros(n, bool)
            wake[g.src[changed[g.dst]]] = True
            active = (active & ~cand) | wake
            labels = new
            delta += int(changed.sum())
        it += 1
    return labels, it


def split_last(g: DirectedCsr, comm: np.ndarray) -> np.ndarray:
    """Name each (community, component) by its smallest vertex id."""
    same = comm[g.src] == comm[g.dst]
    adj = coo_matrix((np.ones(int(same.sum()), np.int8),
                      (g.src[same], g.dst[same])), shape=(g.n, g.n))
    _, comp = connected_components(adj, directed=False)
    first = np.full(comp.max() + 1, g.n, np.int64)
    np.minimum.at(first, comp, np.arange(g.n))
    return first[comp]


def compact(labels: np.ndarray) -> np.ndarray:
    return np.unique(labels, return_inverse=True)[1].astype(np.int32)


def detect(n: int, edges: np.ndarray, weights=None, dtype=np.float32):
    """Compacted GSL-LPA labels and the propagation's iteration count."""
    g = DirectedCsr(n, edges, weights)
    comm, iterations = propagate(g, dtype)
    return compact(split_last(g, comm)), iterations


def mismatched_vertices(labels: np.ndarray, expected: np.ndarray) -> int:
    """Vertices whose label differs; a missing or misshapen answer counts
    every vertex."""
    labels = np.asarray(labels)
    if labels.shape != expected.shape:
        return int(expected.size)
    return int(np.count_nonzero(labels != expected))
