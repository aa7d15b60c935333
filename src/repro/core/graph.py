"""Graph representation for the GSL-LPA engine.

A ``Graph`` is an immutable pytree holding a padded CSR / edge-list hybrid:
edges are stored *directed both ways* (undirected graph semantics, as in the
paper) and sorted by source vertex, so the ``src`` array is the CSR expansion
of ``row_ptr``.  Padding slots (up to ``m_pad``, a multiple of 128 for TPU
alignment) carry ``src = dst = 0``, ``wgt = 0`` and ``edge_mask = False``.

Host-side construction is numpy; the resulting arrays are device arrays.
Static metadata (``n``, ``m_pad``, ``num_edges``) lives in pytree aux data so
jitted functions specialise on shape, never on content.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_LANE = 128  # TPU lane alignment for padded edge arrays.
_SUBLANE = 8  # TPU sublane count: tile rows and the narrowest tile width.


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def next_pow2(x: int, floor: int = 1) -> int:
    return max(int(floor), 1 << max(int(x) - 1, 0).bit_length())


def tile_width(d_real: int, *, exact: bool = False) -> int:
    """Width of a padded neighbor-tile row for maximum degree ``d_real``.

    Below 128 the row is the next power of two of the degree, at least 8
    (the sublane count, so a row tile's (B, 8, 8) equality cube is one
    vreg row group): a degree-4 road graph gathers over 8 slots a row,
    not 128.  From 128 up the row is lane-rounded: the next power of two
    rounded up to 128, or, when ``exact``, the degree itself rounded up
    to 128.
    """
    d_real = max(int(d_real), 1)
    pow2 = next_pow2(d_real)
    if pow2 < _LANE:
        return max(pow2, _SUBLANE)
    return _round_up(d_real if exact else pow2, _LANE)


@partial(jax.tree_util.register_dataclass,
         data_fields=("row_ptr", "src", "dst", "wgt", "edge_mask", "kdeg"),
         meta_fields=("n", "m_pad", "num_edges"))
@dataclasses.dataclass(frozen=True)
class Graph:
    """Padded undirected graph (both edge directions materialised)."""
    # --- static metadata ---
    n: int          # number of vertices
    m_pad: int      # padded directed edge count (multiple of 128)
    num_edges: int  # actual directed edge count (2x undirected)
    # --- arrays ---
    row_ptr: jnp.ndarray   # (n + 1,) int32, CSR offsets into src/dst/wgt
    src: jnp.ndarray       # (m_pad,) int32, edge sources (sorted)
    dst: jnp.ndarray       # (m_pad,) int32, edge destinations
    wgt: jnp.ndarray       # (m_pad,) float32, edge weights (0 on padding)
    edge_mask: jnp.ndarray  # (m_pad,) bool, True for real edges
    kdeg: jnp.ndarray      # (n,) float32, weighted degree K_i

    @property
    def total_weight(self) -> jnp.ndarray:
        """Sum of directed edge weights == 2m in the paper's notation."""
        return jnp.sum(self.wgt)

    def degrees(self) -> jnp.ndarray:
        return self.row_ptr[1:] - self.row_ptr[:-1]


def build_graph(edges: np.ndarray, weights: np.ndarray | None = None,
                n: int | None = None, symmetrize: bool = True) -> Graph:
    """Build a :class:`Graph` from an undirected edge list.

    Args:
      edges: (E, 2) int array of endpoints.  Self loops are dropped
        (``scanCommunities`` excludes i == j).  Duplicate edges are merged
        with their weights summed.
      weights: (E,) float array; defaults to unit weights (paper default).
      n: vertex count; defaults to ``edges.max() + 1``.
      symmetrize: materialise both directions (paper: undirected).
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if weights is None:
        weights = np.ones(len(edges), dtype=np.float64)
    weights = np.asarray(weights, dtype=np.float64).reshape(-1)
    if n is None:
        n = int(edges.max()) + 1 if len(edges) else 1

    keep = edges[:, 0] != edges[:, 1]
    edges, weights = edges[keep], weights[keep]
    if symmetrize:
        edges = np.concatenate([edges, edges[:, ::-1]], axis=0)
        weights = np.concatenate([weights, weights], axis=0)

    # Merge duplicates: sort by (src, dst), sum weights over runs (in the
    # sorted order, one run after another).
    key = edges[:, 0] * n + edges[:, 1]
    order = np.argsort(key, kind="stable")
    key, weights = key[order], weights[order]
    run_start = np.ones(len(key), dtype=bool)
    run_start[1:] = key[1:] != key[:-1]
    uniq = key[run_start]
    wsum = np.bincount(np.cumsum(run_start) - 1, weights=weights,
                       minlength=len(uniq))
    usrc = (uniq // n).astype(np.int32)
    udst = (uniq % n).astype(np.int32)

    num_edges = len(uniq)
    m_pad = max(_round_up(num_edges, _LANE), _LANE)
    src = np.zeros(m_pad, dtype=np.int32)
    dst = np.zeros(m_pad, dtype=np.int32)
    wgt = np.zeros(m_pad, dtype=np.float32)
    mask = np.zeros(m_pad, dtype=bool)
    src[:num_edges], dst[:num_edges] = usrc, udst
    wgt[:num_edges] = wsum.astype(np.float32)
    mask[:num_edges] = True

    row_ptr = np.zeros(n + 1, dtype=np.int64)
    row_ptr[1:] = np.cumsum(np.bincount(usrc, minlength=n))
    row_ptr = row_ptr.astype(np.int32)

    kdeg = np.bincount(usrc, weights=wsum, minlength=n)

    graph = Graph(
        n=int(n), m_pad=int(m_pad), num_edges=int(num_edges),
        row_ptr=jnp.asarray(row_ptr),
        src=jnp.asarray(src), dst=jnp.asarray(dst), wgt=jnp.asarray(wgt),
        edge_mask=jnp.asarray(mask), kdeg=jnp.asarray(kdeg, dtype=jnp.float32),
    )
    # Fingerprint eagerly while the CSR is still host memory: every later
    # graph_fingerprint() (warm-cache lookups, StreamSession bookkeeping)
    # is then a dict read instead of a device->host copy + CRC.
    _set_fingerprint(graph, row_ptr, dst)
    return graph


def _set_fingerprint(graph: Graph, row_ptr: np.ndarray,
                     dst: np.ndarray) -> None:
    """Attach the structural fingerprint from host-side CSR arrays."""
    import zlib
    fp = (graph.n, graph.num_edges,
          zlib.crc32(np.ascontiguousarray(row_ptr).tobytes()),
          zlib.crc32(np.ascontiguousarray(dst).tobytes()))
    object.__setattr__(graph, "_fingerprint", fp)


def graph_fingerprint(graph: Graph) -> tuple:
    """Cheap structural identity: (n, m, crc of offsets, crc of dst).

    Used by the engine's ``warm_start="auto"`` keying — two graphs that
    merely share a vertex count must not warm-start off each other.
    Weights are deliberately excluded: a re-weighted graph keeps the same
    structure and its old labels remain a sound starting point.

    The result is memoized on the instance (frozen dataclass, hence the
    ``object.__setattr__``): re-fitting the same Graph object — the
    warm-start serving pattern — pays the O(m) device-to-host copy and
    CRC only once.
    """
    fp = getattr(graph, "_fingerprint", None)
    if fp is None:
        import zlib
        fp = (graph.n, graph.num_edges,
              zlib.crc32(np.asarray(graph.row_ptr).tobytes()),
              zlib.crc32(np.asarray(graph.dst).tobytes()))
        object.__setattr__(graph, "_fingerprint", fp)
    return fp


def to_numpy_adj(graph: Graph) -> list[list[tuple[int, float]]]:
    """Host adjacency list (for the BFS oracle / host split path)."""
    src = np.asarray(graph.src)[: graph.num_edges]
    dst = np.asarray(graph.dst)[: graph.num_edges]
    wgt = np.asarray(graph.wgt)[: graph.num_edges]
    adj: list[list[tuple[int, float]]] = [[] for _ in range(graph.n)]
    for s, d, w in zip(src.tolist(), dst.tolist(), wgt.tolist()):
        adj[s].append((d, w))
    return adj


def to_padded_neighbors(graph: Graph, d_max: int | None = None,
                        ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Dense padded neighbor matrices for the Pallas tile path.

    Returns (nbr, nw, nmask) with shapes (n_pad, d_max): neighbor vertex ids,
    weights, and validity.  ``n_pad`` rounds n up to 8 (sublane); ``d_max``
    is the row width as given, by default ``tile_width`` of the maximum
    degree.  Pad neighbor ids point at the row vertex itself with weight 0
    (self edges are excluded by construction, so a 0-weight self slot can
    never win the argmax).  A ``d_max`` below the maximum degree raises:
    dropping edges would change the answer.
    """
    row_ptr = np.asarray(graph.row_ptr).astype(np.int64)
    dst = np.asarray(graph.dst)[: graph.num_edges]
    wgt = np.asarray(graph.wgt)[: graph.num_edges]
    deg = row_ptr[1:] - row_ptr[:-1]
    d_real = int(deg.max()) if len(deg) else 0
    if d_max is None:
        d_max = tile_width(d_real)
    if d_max < d_real:
        raise ValueError(f"d_max={d_max} is below the graph's maximum "
                         f"degree {d_real}; its edges would be dropped")
    n_pad = _round_up(graph.n, _SUBLANE)

    nbr = np.repeat(np.arange(n_pad, dtype=np.int32)[:, None], d_max, axis=1)
    nw = np.zeros((n_pad, d_max), dtype=np.float32)
    nmask = np.zeros((n_pad, d_max), dtype=bool)
    rows = np.repeat(np.arange(graph.n), deg)
    cols = np.arange(len(rows)) - np.repeat(row_ptr[:-1], deg)
    nbr[rows, cols] = dst[: len(rows)]
    nw[rows, cols] = wgt[: len(rows)]
    nmask[rows, cols] = True
    return nbr, nw, nmask
