"""Shape bucketing: pad graphs to canonical shapes so jit caches hit.

Every distinct (vertex-count, edge-count, max-degree) shape triple would
otherwise force a fresh trace+compile — fatal for a service ingesting a
stream of graphs.  Bucketing rounds each dimension up to the next power of
two (with configurable floors), pads the graph with isolated vertices and
masked edges to the bucket shape, and keys the engine's compile cache on
the bucket.  Padded vertices have no edges, so they can never adopt or
donate a label; the only semantic coupling is the convergence threshold,
which the backends compute from the *real* vertex count passed as a traced
scalar (see ``lpa_run``'s ``n_real``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph, _round_up, next_pow2, tile_width


class BucketKey(NamedTuple):
    """Canonical padded shapes — the compile-cache key's shape component."""
    n: int   # vertex bucket (>= real n)
    m: int   # directed-edge bucket (>= real m_pad; multiple of 128)
    d: int   # tile row width, ``tile_width`` of the max degree (tile/sharded)


class BatchBucketKey(NamedTuple):
    """Batched-dispatch bucket: graph-count + packed-total shapes.

    Mixed traffic reuses compiled batch plans as long as the *totals*
    land in the same bucket — the per-graph composition rides along as
    traced data (sizes / graph_id / voffset arrays).
    """
    k: int   # graph-count bucket (>= real batch size)
    n: int   # total-vertex bucket (>= packed n)
    m: int   # total-edge bucket (>= packed m_pad; multiple of 128)
    d: int   # tile row width for the max member degree (``tile_width``)


def max_degree(graph: Graph) -> int:
    deg = np.asarray(graph.row_ptr[1:]) - np.asarray(graph.row_ptr[:-1])
    return int(deg.max()) if len(deg) else 1


def tile_rows(bucket_n: int) -> int:
    """Row count of the padded tiles for a vertex bucket (sublane-aligned)."""
    return _round_up(bucket_n, 8)


def vertex_degree_bucket(n: int, d_real: int, *, bucketing: str = "pow2",
                         min_vertex_bucket: int = 256) -> tuple[int, int]:
    """(vertex bucket, tile row width) for ``n`` vertices of maximum
    degree ``d_real`` — the tile shapes a plan compiles at."""
    d = tile_width(d_real, exact=bucketing == "exact")
    if bucketing == "exact":
        return n, d
    return next_pow2(n, min_vertex_bucket), d


def bucket_for(graph: Graph, *, bucketing: str = "pow2",
               min_vertex_bucket: int = 256,
               min_edge_bucket: int = 2048) -> BucketKey:
    n, d = vertex_degree_bucket(graph.n, max_degree(graph),
                                bucketing=bucketing,
                                min_vertex_bucket=min_vertex_bucket)
    m = graph.m_pad if bucketing == "exact" \
        else next_pow2(graph.m_pad, min_edge_bucket)
    return BucketKey(n=n, m=m, d=d)


def batch_bucket_for(batch, *, bucketing: str = "pow2",
                     min_vertex_bucket: int = 256,
                     min_edge_bucket: int = 2048) -> BatchBucketKey:
    """Bucket a :class:`repro.core.batch.GraphBatch`'s packed shapes."""
    g = batch.graph
    n, d = vertex_degree_bucket(g.n, max_degree(g), bucketing=bucketing,
                                min_vertex_bucket=min_vertex_bucket)
    if bucketing == "exact":
        return BatchBucketKey(k=batch.num_graphs, n=n, m=g.m_pad, d=d)
    return BatchBucketKey(k=next_pow2(batch.num_graphs), n=n,
                          m=next_pow2(g.m_pad, min_edge_bucket), d=d)


def batch_index_arrays(batch, k_bucket: int, n_rows: int,
                       ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-slot / per-vertex index arrays for the batched kernels.

    Returns (sizes, graph_id, voffset):
      sizes    (k_bucket + 1,) int32 — real vertex count per slot; empty
               slots and the final padding slot carry 0, so they are
               converged from the first iteration.
      graph_id (n_rows,) int32 — owning slot per row; padding rows map to
               the extra slot ``k_bucket``.
      voffset  (n_rows,) int32 — owning slot's vertex-id offset (padding
               rows use the packed vertex count, keeping local ids
               well-defined).
    """
    k1 = k_bucket + 1
    nt = batch.total_vertices
    sizes = np.zeros(k1, np.int32)
    sizes[:batch.num_graphs] = batch.sizes
    graph_id = np.full(n_rows, k_bucket, np.int32)
    graph_id[:nt] = batch.graph_id
    voffset = np.full(n_rows, nt, np.int32)
    voffset[:nt] = batch.vertex_offsets()
    return sizes, graph_id, voffset


def pad_graph(graph: Graph, bucket: BucketKey) -> Graph:
    """Pad a graph up to its bucket shape (no-op when already there).

    Vertices ``graph.n .. bucket.n`` are isolated; edge slots up to
    ``bucket.m`` are masked out.  The padded graph's static metadata is a
    pure function of the bucket, so every graph in a bucket produces the
    same jit cache key.  ``num_edges`` is deliberately set to the bucket
    edge count — host-side helpers (``to_numpy_adj`` etc.) must be given
    the *original* graph, never a bucketed one.
    """
    if graph.n == bucket.n and graph.m_pad == bucket.m:
        return graph
    if graph.n > bucket.n or graph.m_pad > bucket.m:
        raise ValueError(f"graph (n={graph.n}, m_pad={graph.m_pad}) exceeds "
                         f"bucket {bucket}")
    extra_m = bucket.m - graph.m_pad
    extra_n = bucket.n - graph.n

    def pad1(a, amount, value=0):
        return jnp.pad(a, (0, amount), constant_values=value)

    row_ptr = jnp.concatenate([
        graph.row_ptr,
        jnp.full((extra_n,), graph.row_ptr[-1], dtype=graph.row_ptr.dtype),
    ]) if extra_n else graph.row_ptr
    return Graph(
        n=bucket.n, m_pad=bucket.m, num_edges=bucket.m,
        row_ptr=row_ptr,
        src=pad1(graph.src, extra_m),
        dst=pad1(graph.dst, extra_m),
        wgt=pad1(graph.wgt, extra_m),
        edge_mask=pad1(graph.edge_mask, extra_m),
        kdeg=pad1(graph.kdeg, extra_n),
    )


def pad_active(active: np.ndarray | None, n_real: int,
               n_bucket: int) -> np.ndarray:
    """Pad an (n_real,) unprocessed-seed mask to the bucket.

    ``None`` (a full detection) seeds every row active — bit-identical
    to the pre-init_active behaviour, including the padded rows, which
    are edgeless and therefore inert either way.  An explicit mask (a
    delta's affected frontier) seeds padded rows asleep.
    """
    if active is None:
        return np.ones(n_bucket, dtype=bool)
    active = np.asarray(active, dtype=bool).reshape(-1)
    if len(active) != n_real:
        raise ValueError(f"init_active has {len(active)} entries for a "
                         f"graph with {n_real} vertices")
    if n_bucket == n_real:
        return active
    return np.concatenate([active, np.zeros(n_bucket - n_real, dtype=bool)])


def pad_labels(labels: np.ndarray, n_real: int, n_bucket: int) -> np.ndarray:
    """Pad an (n_real,) init-label vector to the bucket: padded vertices
    keep their own ids (singleton communities, the LPA invariant)."""
    labels = np.asarray(labels, dtype=np.int32).reshape(-1)
    if len(labels) != n_real:
        raise ValueError(f"init_labels has {len(labels)} entries for a "
                         f"graph with {n_real} vertices")
    if np.any(labels < 0) or np.any(labels >= n_real):
        raise ValueError("init_labels must be vertex-id-valued in [0, n)")
    if n_bucket == n_real:
        return labels
    return np.concatenate(
        [labels, np.arange(n_real, n_bucket, dtype=np.int32)])
