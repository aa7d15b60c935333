"""Multi-graph batching: disjoint-union packing + batched LPA/split.

GSL-LPA's labels are vertex ids and label propagation never crosses a
missing edge, so k graphs packed as a *disjoint union* (concatenated CSR
arrays with per-graph vertex-id offsets and no inter-graph edges)
propagate independently inside one kernel launch — a single device
dispatch amortises per-launch overhead across the whole batch.

Exact per-graph parity with ``Engine.fit`` requires care in two places:

* **Local label coordinates.**  The tie-break hash and the parity
  classes are functions of raw label / vertex-id values, so a packed run
  over *global* ids would break ties differently from a standalone run.
  The batched kernels therefore keep every vertex's label in its graph's
  *local* id space (value in ``[0, n_i)``) while gathers still use global
  row indices; ``voffset`` (per-vertex owner offset) converts between the
  two where needed (the split shortcut's pointer jump).
* **Per-graph convergence.**  Each member graph must stop exactly where
  its standalone run would: the batched loops track a per-graph ``done``
  flag (frozen graphs stop producing candidates) and per-graph iteration
  counters, advancing the global loop until every member has converged.
  Early-converged members ride along as no-ops — their labels are at a
  sweep fixpoint, so the extra sweeps cannot change them.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import _LANE, Graph, _round_up
from repro.core.lpa import _label_hash, lpa_move, neighbors_of
from repro.core.split import _min_label_sweep


@dataclasses.dataclass(frozen=True)
class GraphBatch:
    """k graphs packed into one disjoint-union super-graph.

    ``graph`` is a normal :class:`Graph` (member padding stripped, one
    shared padded tail), so every single-graph code path — bucketing,
    ``pad_graph``, ``to_padded_neighbors`` — applies unchanged.  The
    batch metadata stays host-side numpy.
    """
    graph: Graph             # packed super-graph (no inter-graph edges)
    sizes: np.ndarray        # (k,) int64 per-graph vertex counts
    offsets: np.ndarray      # (k + 1,) int64 vertex-id offset per graph
    edge_counts: np.ndarray  # (k,) int64 per-graph directed edge counts
    graph_id: np.ndarray     # (total_vertices,) int32 owner of each vertex

    @property
    def num_graphs(self) -> int:
        return len(self.sizes)

    @property
    def total_vertices(self) -> int:
        return int(self.offsets[-1])

    @property
    def total_edges(self) -> int:
        return int(self.edge_counts.sum())

    @classmethod
    def pack(cls, graphs) -> "GraphBatch":
        """Disjoint-union pack: offset vertex ids, concatenate CSR arrays.

        Member graphs' own edge padding is stripped; each member's edges
        are already sorted by (src, dst) and offsets are increasing, so
        the concatenation stays a valid CSR ordering.  Handles n=0 and
        edgeless members.
        """
        graphs = list(graphs)
        if not graphs:
            raise ValueError("GraphBatch.pack needs at least one graph")
        sizes = np.array([g.n for g in graphs], dtype=np.int64)
        offsets = np.concatenate([np.zeros(1, np.int64), np.cumsum(sizes)])
        edge_counts = np.array([g.num_edges for g in graphs], dtype=np.int64)
        n_total = int(offsets[-1])
        m_total = int(edge_counts.sum())

        srcs, dsts, wgts, kdegs, degs = [], [], [], [], []
        for g, off in zip(graphs, offsets[:-1]):
            e = g.num_edges
            srcs.append(np.asarray(g.src)[:e].astype(np.int64) + off)
            dsts.append(np.asarray(g.dst)[:e].astype(np.int64) + off)
            wgts.append(np.asarray(g.wgt)[:e])
            kdegs.append(np.asarray(g.kdeg, dtype=np.float32))
            rp = np.asarray(g.row_ptr)
            degs.append((rp[1:] - rp[:-1]).astype(np.int64))

        m_pad = max(_round_up(m_total, _LANE), _LANE)
        src = np.zeros(m_pad, np.int32)
        dst = np.zeros(m_pad, np.int32)
        wgt = np.zeros(m_pad, np.float32)
        mask = np.zeros(m_pad, bool)
        src[:m_total] = np.concatenate(srcs)
        dst[:m_total] = np.concatenate(dsts)
        wgt[:m_total] = np.concatenate(wgts)
        mask[:m_total] = True
        row_ptr = np.concatenate(
            [np.zeros(1, np.int64),
             np.cumsum(np.concatenate(degs))]).astype(np.int32)
        graph_id = np.repeat(np.arange(len(graphs), dtype=np.int32), sizes)

        packed = Graph(
            n=n_total, m_pad=int(m_pad), num_edges=m_total,
            row_ptr=jnp.asarray(row_ptr),
            src=jnp.asarray(src), dst=jnp.asarray(dst),
            wgt=jnp.asarray(wgt), edge_mask=jnp.asarray(mask),
            kdeg=jnp.asarray(np.concatenate(kdegs) if kdegs
                             else np.zeros(0, np.float32)),
        )
        return cls(graph=packed, sizes=sizes, offsets=offsets,
                   edge_counts=edge_counts, graph_id=graph_id)

    def vertex_offsets(self) -> np.ndarray:
        """(total_vertices,) int32: each vertex's owning-graph offset."""
        return np.repeat(self.offsets[:-1], self.sizes).astype(np.int32)

    def pack_labels(self, member_labels) -> np.ndarray | None:
        """Concatenate per-member init labels into one packed vector.

        ``member_labels`` is a length-``num_graphs`` sequence; each entry
        is an (n_i,) vertex-id-valued array (*local* coordinates — which
        is exactly what a solo warm start uses, since a standalone
        graph's ids are its local ids) or None for a cold member (kept at
        singleton starts).  Returns a (total_vertices,) int32 vector, or
        None when every member is cold.
        """
        member_labels = list(member_labels)
        if len(member_labels) != self.num_graphs:
            raise ValueError(f"got {len(member_labels)} init-label entries "
                             f"for a batch of {self.num_graphs} graphs")
        if all(lab is None for lab in member_labels):
            return None
        out = np.empty(self.total_vertices, dtype=np.int32)
        for i, lab in enumerate(member_labels):
            lo, hi = int(self.offsets[i]), int(self.offsets[i + 1])
            if lab is None:
                out[lo:hi] = np.arange(hi - lo, dtype=np.int32)
            else:
                out[lo:hi] = np.asarray(lab, dtype=np.int32).reshape(-1)
        return out

    def pack_active(self, member_active) -> np.ndarray | None:
        """Concatenate per-member init active masks (None -> all-active).

        Packed counterpart of the GVE-LPA unprocessed flags: a member's
        mask marks the vertices seeded unprocessed (its delta's affected
        frontier); cold members start fully active.  Returns a
        (total_vertices,) bool vector, or None when every member is
        fully active.
        """
        member_active = list(member_active)
        if len(member_active) != self.num_graphs:
            raise ValueError(f"got {len(member_active)} init-active entries "
                             f"for a batch of {self.num_graphs} graphs")
        if all(act is None for act in member_active):
            return None
        out = np.empty(self.total_vertices, dtype=bool)
        for i, act in enumerate(member_active):
            lo, hi = int(self.offsets[i]), int(self.offsets[i + 1])
            out[lo:hi] = True if act is None \
                else np.asarray(act, dtype=bool).reshape(-1)
        return out

    def unpack(self, labels, compact: bool = True) -> list[np.ndarray]:
        """Slice a packed (>= total_vertices,) label vector per graph.

        ``labels`` is expected in local coordinates (what the batched
        kernels produce); with ``compact=True`` each slice is densely
        relabeled to ``[0, K_i)`` — identical rank order to the engine's
        single-graph compaction.
        """
        labels = np.asarray(labels).reshape(-1)
        if len(labels) < self.total_vertices:
            raise ValueError(f"labels has {len(labels)} entries; batch has "
                             f"{self.total_vertices} vertices")
        out = []
        for i in range(self.num_graphs):
            lo, hi = int(self.offsets[i]), int(self.offsets[i + 1])
            lab = labels[lo:hi].astype(np.int32)
            if compact:
                lab = np.unique(lab, return_inverse=True)[1].astype(np.int32)
            out.append(lab)
        return out


def warm_state_rows(rows: int, voffset, labels0=None, active0=None,
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Extend packed (total_vertices,) warm-start state to ``rows`` rows.

    Bucket-padding rows keep their local singleton ids (``row -
    voffset``, the batched kernels' cold start) and are seeded inactive
    when an explicit active mask is present.  With both inputs None this
    reproduces the cold defaults exactly: local-id labels, all-active.
    """
    voff = np.asarray(voffset).astype(np.int64)
    local = (np.arange(rows, dtype=np.int64) - voff).astype(np.int32)
    if labels0 is None:
        lab = local
    else:
        lab = local.copy()
        lab[: len(labels0)] = np.asarray(labels0, dtype=np.int32)
    if active0 is None:
        act = np.ones(rows, dtype=bool)
    else:
        act = np.zeros(rows, dtype=bool)
        act[: len(active0)] = np.asarray(active0, dtype=bool)
    return lab, act


def lpa_run_batched(graph: Graph, sizes: jnp.ndarray, graph_id: jnp.ndarray,
                    voffset: jnp.ndarray, labels0: jnp.ndarray,
                    active0: jnp.ndarray, *, tau: float, max_iterations: int,
                    profile: bool = False):
    """Batched propagation over a packed graph (traced; jit by the caller).

    graph: packed + bucket-padded super-graph.
    sizes: (k + 1,) traced per-slot real vertex counts (0 for empty slots
      and the padding slot), so one executable serves every batch in the
      bucket.
    graph_id / voffset: (graph.n,) owner slot + owner offset per vertex.
    labels0 / active0: (graph.n,) initial labels (*local* coordinates —
      cold start passes the local ids themselves) and unprocessed-seed
      mask (cold start passes all-True).  Traced, so cold and warm
      dispatches share one compiled executable.

    Returns (labels, iterations): labels in *local* coordinates, plus the
    per-slot iteration counts — each slot stops exactly where its
    standalone ``lpa_run`` would (same threshold arithmetic as the
    traced-``n_real`` path, same hash seeds, same parity classes).

    ``profile``: additionally carry a ``(2 * max_iterations, 2, k1)``
    int32 buffer with per-slot [candidate count, changed count] rows per
    sub-sweep (the batched counterpart of ``lpa_run``'s profile buffer;
    writes never feed back, so labels/iterations stay bit-identical).
    Returns ``(labels, iterations, buffer)``.
    """
    n = graph.n
    k1 = sizes.shape[0]
    vid = jnp.arange(n, dtype=jnp.int32)
    local = vid - voffset
    parity = (_label_hash(local, jnp.int32(-1)) & 1).astype(bool)
    thr = (jnp.float32(tau) * sizes.astype(jnp.float32)).astype(jnp.int32)
    done0 = sizes <= thr

    def cond(s):
        _labels, _active, it, done, _iters = s[:5]
        return jnp.any(~done) & (it < max_iterations)

    def body(s):
        labels, active, it, done, iters = s[:5]
        buf = s[5] if profile else None
        running = ~done[graph_id]
        dn = jnp.zeros((k1,), jnp.int32)
        for sweep, klass in enumerate((~parity, parity)):
            with jax.named_scope("sweep.wake"):
                cand = active & klass & running
            labels, changed, _ = lpa_move(graph, labels, cand,
                                          2 * it + sweep)
            with jax.named_scope("sweep.wake"):
                active = (active & ~cand) | neighbors_of(graph, changed)
                sc = jax.ops.segment_sum(changed.astype(jnp.int32),
                                         graph_id, num_segments=k1)
                dn = dn + sc
            if profile:
                buf = buf.at[2 * it + sweep].set(jnp.stack(
                    [jax.ops.segment_sum(cand.astype(jnp.int32), graph_id,
                                         num_segments=k1), sc]))
        iters = iters + jnp.where(done, 0, 1)
        nxt = (labels, active, it + jnp.int32(1), done | (dn <= thr), iters)
        return nxt + (buf,) if profile else nxt

    state = (labels0.astype(jnp.int32), active0.astype(bool), jnp.int32(0),
             done0, jnp.zeros((k1,), jnp.int32))
    if profile:
        state = state + (jnp.full((2 * max_iterations, 2, k1), -1,
                                  jnp.int32),)
        labels, _, _, _, iters, buf = jax.lax.while_loop(cond, body, state)
        return labels, iters, buf
    labels, _, _, _, iters = jax.lax.while_loop(cond, body, state)
    return labels, iters


def split_lp_batched(graph: Graph, sizes: jnp.ndarray, graph_id: jnp.ndarray,
                     voffset: jnp.ndarray, comm: jnp.ndarray, *,
                     prune: bool = False, shortcut: bool = False,
                     profile_rows: int = 0):
    """Batched Split-Last over a packed graph (local-label coordinates).

    Min-label sweeps are idempotent at a member's fixpoint, so converged
    members simply stop changing while the loop drains the rest; per-slot
    iteration counts record the sweep at which each member's standalone
    ``split_lp`` would have exited.

    ``profile_rows`` (0 = off): carry a ``(profile_rows, 2, k1)`` int32
    per-slot [active count, changed count] buffer per sweep (rows past
    the cap overwrite the last; writes never feed back).  Returns
    ``(labels, iterations, buffer)``.
    """
    n = graph.n
    k1 = sizes.shape[0]
    local = jnp.arange(n, dtype=jnp.int32) - voffset
    done0 = sizes == 0

    def cond(s):
        _labels, _active, done, _iters = s[:4]
        return jnp.any(~done)

    def body(s):
        labels, active, done, iters = s[:4]
        buf = s[4] if profile_rows else None
        new, nxt_active, changed, _ = _min_label_sweep(
            graph, comm, labels, active, prune, shortcut, voffset=voffset)
        with jax.named_scope("sweep.wake"):
            dn = jax.ops.segment_sum(changed.astype(jnp.int32), graph_id,
                                     num_segments=k1)
        if profile_rows:
            row = jnp.minimum(iters.max(), profile_rows - 1)
            buf = buf.at[row].set(jnp.stack(
                [jax.ops.segment_sum(active.astype(jnp.int32), graph_id,
                                     num_segments=k1), dn]))
        iters = iters + jnp.where(done, 0, 1)
        nxt = (new, nxt_active, done | (dn == 0), iters)
        return nxt + (buf,) if profile_rows else nxt

    state = (local, jnp.ones(n, dtype=bool), done0,
             jnp.zeros((k1,), jnp.int32))
    if profile_rows:
        state = state + (jnp.full((profile_rows, 2, k1), -1, jnp.int32),)
        labels, _, _, iters, buf = jax.lax.while_loop(cond, body, state)
        return labels, iters, buf
    labels, _, _, iters = jax.lax.while_loop(cond, body, state)
    return labels, iters
