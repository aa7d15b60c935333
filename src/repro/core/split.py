"""Split-Last (SL): separate internally-disconnected communities.

Implements the paper's three techniques (Section 4):

* ``split_lp``   — Algorithm 1, minimum-label Label Propagation (SL-LP).
* ``split_lpp``  — Algorithm 1 with pruning (SL-LPP).
* ``split_bfs_host`` — Algorithm 2, per-community BFS.  BFS worklists are
  inherently sequential per component; this is the paper's preferred *CPU*
  technique and is kept as the host execution path / test oracle.  On TPU the
  production path is LP/LPP (see DESIGN.md §2 — the CPU ranking flips).

Beyond-paper optimization: ``shortcut=True`` adds Shiloach-Vishkin pointer
shortcutting (``L <- min(L, L[L])`` after each neighbor-min sweep).  Labels
always point at a vertex in the same community and component, so adopting the
label's label is sound; it collapses convergence from O(component diameter)
to O(log diameter) sweeps.  Disabled by default for paper-faithful runs.
"""
from __future__ import annotations

from collections import deque
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rowscan
from repro.core.graph import Graph, to_numpy_adj

_INT_MAX = jnp.iinfo(jnp.int32).max  # above every label: "no neighbour"


class SplitState(NamedTuple):
    labels: jnp.ndarray     # (n,) int32 minimum-label per (community, component)
    active: jnp.ndarray     # (n,) bool  pruning flags (LPP only; all-True for LP)
    iterations: jnp.ndarray  # () int32
    delta_n: jnp.ndarray    # () int32


def _min_label_sweep(graph: Graph, comm: jnp.ndarray, labels: jnp.ndarray,
                     active: jnp.ndarray, prune: bool, shortcut: bool,
                     voffset: jnp.ndarray | None = None,
                     label_bound: jnp.ndarray | int | None = None):
    """One sweep of Algorithm 1's loop body (lines 8-21), vectorised.

    ``voffset``: per-vertex owner offsets when labels are in per-graph
    *local* coordinates (the batched path) — the shortcut's pointer jump
    must gather at the label's global row, ``label + voffset``.

    ``label_bound``: exclusive upper bound on real label values, used as
    the no-same-community-neighbor sentinel.  Defaults to ``graph.n``;
    the out-of-core partition path sweeps compact local row spaces whose
    labels are *global* vertex ids and passes the full graph's vertex
    count (traced — one executable serves every partition).
    """
    bound = graph.n if label_bound is None else label_bound
    with jax.named_scope("sweep.reduce"):
        keys = rowscan.row_keys(graph)
        comm_src = rowscan.row_broadcast(comm, graph.row_ptr, graph.m_pad)
    with jax.named_scope("sweep.gather"):
        same = graph.edge_mask & (comm_src == comm[graph.dst])
        # min over same-community neighbors; sentinel `bound` elsewhere
        cand = jnp.where(same, labels[graph.dst], bound).astype(jnp.int32)
    with jax.named_scope("sweep.reduce"):
        nbr_min = rowscan.row_reduce(keys, cand, graph.row_ptr, jnp.minimum,
                                     _INT_MAX)
        new = jnp.minimum(labels, nbr_min.astype(labels.dtype))
        if prune:
            new = jnp.where(active, new, labels)
    if shortcut:  # pointer jump (beyond-paper)
        with jax.named_scope("sweep.gather"):
            new = jnp.minimum(
                new, new[new if voffset is None else new + voffset])
    with jax.named_scope("sweep.wake"):
        changed = new != labels
        delta_n = jnp.sum(changed.astype(jnp.int32))
        if prune:
            # reactivate same-community neighbors of changed vertices
            # (lines 20-21)
            nxt_active = rowscan.row_reduce(
                keys, (changed[graph.dst] & same).astype(jnp.int32),
                graph.row_ptr, jnp.maximum, 0) > 0
        else:
            nxt_active = active
    return new, nxt_active, changed, delta_n


@partial(jax.jit, static_argnames=("prune", "shortcut", "profile_rows"))
def split_lp(graph: Graph, comm: jnp.ndarray, prune: bool = False,
             shortcut: bool = False, profile_rows: int = 0,
             n_real: jnp.ndarray | None = None):
    """Algorithm 1: SL-LP (``prune=False``) / SL-LPP (``prune=True``).

    Returns labels where each vertex carries the minimum vertex id reachable
    within (its community x its connected component) — i.e. one unique label
    per component per community, which is exactly the split partition.

    ``profile_rows`` (static, 0 = off): carry a ``(profile_rows, 3)``
    int32 buffer writing [active count, changed count, sweep index] per
    sweep (rows past the cap overwrite the last — the caller flags
    truncation from the iteration count).  Buffer writes never feed back,
    so profiled runs stay bit-identical; returns ``(SplitState, buffer)``.
    ``n_real`` (traced, optional) masks bucket-padding vertices out of
    the recorded active counts — it does not affect the sweep itself.
    """
    n = graph.n
    comm = comm.astype(jnp.int32)
    state = SplitState(labels=jnp.arange(n, dtype=jnp.int32),
                       active=jnp.ones(n, dtype=bool),
                       iterations=jnp.int32(0), delta_n=jnp.int32(n))
    real = (jnp.ones(n, dtype=bool) if n_real is None
            else jnp.arange(n, dtype=jnp.int32) < n_real)

    def cond(carry):
        s = carry[0] if profile_rows else carry
        return s.delta_n > 0

    def body(carry):
        s, buf = carry if profile_rows else (carry, None)
        new, nxt_active, _, dn = _min_label_sweep(
            graph, comm, s.labels, s.active, prune, shortcut)
        if profile_rows:
            row = jnp.minimum(s.iterations, profile_rows - 1)
            buf = buf.at[row].set(jnp.stack(
                [jnp.sum((s.active & real).astype(jnp.int32)), dn,
                 s.iterations]))
        nxt = SplitState(new, nxt_active, s.iterations + 1, dn)
        return (nxt, buf) if profile_rows else nxt

    if profile_rows:
        buf0 = jnp.full((profile_rows, 3), -1, jnp.int32)
        return jax.lax.while_loop(cond, body, (state, buf0))
    return jax.lax.while_loop(cond, body, state)


def split_lpp(graph: Graph, comm: jnp.ndarray, shortcut: bool = False):
    return split_lp(graph, comm, prune=True, shortcut=shortcut)


@partial(jax.jit, static_argnames=("prune",))
def min_label_sweep(graph: Graph, comm: jnp.ndarray, labels: jnp.ndarray,
                    active: jnp.ndarray, label_bound: jnp.ndarray,
                    prune: bool = False) -> jnp.ndarray:
    """Partition-local split sweep: one Algorithm-1 step over a CSR slice.

    The out-of-core driver (:mod:`repro.partition.ooc`) runs the §3.3
    split phase one partition at a time: ``graph`` is a compact local
    subgraph (partition rows followed by halo rows), ``comm`` / ``labels``
    carry *global* community ids and split labels gathered for those rows,
    and ``label_bound`` is the full graph's vertex count.  Because the
    sweep is synchronous (new labels are a pure function of the pre-sweep
    snapshot), sweeping partitions sequentially against a shared snapshot
    and double-buffering the results is bit-identical to the in-core
    :func:`split_lp` sweep — the cross-partition label unification is the
    outer fixed-point loop over these sweeps.  The pointer-shortcut jump
    needs the full label array, so it is *not* applied here; the driver
    applies it globally after assembling the sweep (same ordering as the
    in-core sweep body).  Returns the new labels (pre-shortcut).
    """
    new, _, _, _ = _min_label_sweep(graph, comm, labels, active,
                                    prune=prune, shortcut=False,
                                    label_bound=label_bound)
    return new


@jax.jit
def min_label_wake(graph: Graph, comm: jnp.ndarray,
                   changed: jnp.ndarray) -> jnp.ndarray:
    """Pruning reactivation for a partition-local split sweep.

    A vertex re-enters the SL-LPP worklist exactly when one of its
    same-community neighbors changed label in the previous sweep
    (Algorithm 1 lines 20-21).  ``changed`` holds the previous sweep's
    global changed flags gathered to this slice's local rows; only the
    slice's own edges are needed because the reactivation rule reads each
    vertex's *own* neighborhood.
    """
    with jax.named_scope("sweep.wake"):
        comm_src = rowscan.row_broadcast(comm, graph.row_ptr, graph.m_pad)
        same = graph.edge_mask & (comm_src == comm[graph.dst])
        return rowscan.row_reduce(
            rowscan.row_keys(graph),
            (changed[graph.dst] & same).astype(jnp.int32), graph.row_ptr,
            jnp.maximum, 0) > 0


def split_bfs_host(graph: Graph, comm: np.ndarray) -> np.ndarray:
    """Algorithm 2: per-community BFS splitting (host / oracle path).

    Sequential-per-component frontier BFS with the paper's semantics: each
    still-unvisited vertex seeds a BFS restricted to its community; all
    reached vertices adopt the seed's id as their new community label.
    """
    adj = to_numpy_adj(graph)
    comm = np.asarray(comm)
    n = graph.n
    out = np.arange(n, dtype=np.int32)
    visited = np.zeros(n, dtype=bool)
    for i in range(n):
        if visited[i]:
            continue
        visited[i] = True
        q = deque([i])
        while q:
            u = q.popleft()
            out[u] = i
            for v, _w in adj[u]:
                if not visited[v] and comm[v] == comm[i]:
                    visited[v] = True
                    q.append(v)
    return out


def compact_labels(labels: jnp.ndarray) -> jnp.ndarray:
    """Relabel communities to a dense [0, K) range (jit-able, any values)."""
    sort_lab = jnp.sort(labels)
    is_new = jnp.concatenate([jnp.ones((1,), bool),
                              sort_lab[1:] != sort_lab[:-1]])
    rank_at_pos = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    idx = jnp.searchsorted(sort_lab, labels, side="left")
    return rank_at_pos[idx].astype(jnp.int32)


def num_communities(labels: jnp.ndarray) -> jnp.ndarray:
    sort_lab = jnp.sort(labels)
    is_new = jnp.concatenate([jnp.ones((1,), bool), sort_lab[1:] != sort_lab[:-1]])
    return jnp.sum(is_new.astype(jnp.int32))
