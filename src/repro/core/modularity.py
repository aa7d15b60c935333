"""Modularity (paper Eq. 1), computed with segment reductions.

With both edge directions stored, let S = sum of directed weights = 2m,
in_c = directed weight inside community c, K_c = sum of weighted degrees in
community c.  Then  Q = sum_c [ in_c / S - (K_c / S)^2 ].

The sums are float32 on the device, so none of them is a scatter-add: a
scatter accumulates each community's terms one after another, which stops
counting at 2^24 unit terms (a community of a graph with 10^8 edges can
hold more) and drops small terms once the running sum is large.  ``sum_c in_c``
is one reduction over the edges, and each ``K_c`` is added in a tree after
a sort by community.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph


_FAN_IN = 16


def _community_sums(values: jnp.ndarray, comm: jnp.ndarray) -> jnp.ndarray:
    """Each community's sum of ``values``, added in a tree of fan-in 16.

    The values are sorted by community.  Level k sums each community's
    values within aligned blocks of 16^k positions, from the 16 partial
    sums of level k-1 (kept at the first position of their block, zeros
    elsewhere), until a block spans the whole array.  No accumulator adds
    more than 16 nonzero terms, so the float32 error grows with the tree's
    depth, not the community's size.  Returns each community's sum at its
    first sorted position and 0 elsewhere.
    """
    n = values.shape[0]
    c, v = jax.lax.sort((comm, values), num_keys=1)
    pos = jnp.arange(n)
    width = 1
    while width < n:
        width *= _FAN_IN
        block = pos // width
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 (c[1:] != c[:-1]) | (block[1:] != block[:-1])])
        slot = jnp.cumsum(first.astype(jnp.int32)) - 1
        sums = jax.ops.segment_sum(v, slot, num_segments=n,
                                   indices_are_sorted=True)
        v = jnp.where(first, sums[slot], 0.0)
    return v


@jax.jit
def modularity(graph: Graph, comm: jnp.ndarray) -> jnp.ndarray:
    comm = comm.astype(jnp.int32)
    s = jnp.maximum(graph.total_weight, 1e-30)  # empty graph: Q := 0, not NaN
    within = graph.edge_mask & (comm[graph.src] == comm[graph.dst])
    in_total = jnp.sum(jnp.where(within, graph.wgt, 0.0))
    k_c = _community_sums(graph.kdeg, comm)
    return in_total / s - jnp.sum((k_c / s) ** 2)


def modularity_host(graph: Graph, comm) -> float:
    """Float64 host oracle of :func:`modularity`, from the CSR."""
    row_ptr = np.asarray(graph.row_ptr).astype(np.int64)
    m = int(row_ptr[-1])
    src = np.repeat(np.arange(graph.n), np.diff(row_ptr))
    dst = np.asarray(graph.dst)[:m]
    w = np.asarray(graph.wgt)[:m].astype(np.float64)
    comm = np.asarray(comm).astype(np.int64)
    s = max(w.sum(), 1e-30)
    within = w[comm[src] == comm[dst]].sum()
    k = np.bincount(src, weights=w, minlength=graph.n)
    _, k_c = np.unique(comm, return_inverse=True)
    k_c = np.bincount(k_c, weights=k)
    return float(within / s - np.sum((k_c / s) ** 2))
