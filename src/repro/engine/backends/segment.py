"""Segment backend: the CSR edge-list sort + segment-reduce path.

Wraps ``core.lpa.lpa_run`` (propagation) and ``core.split.split_lp``
(Split-Last) behind the Backend protocol.  The plan's jitted wrappers
close over the algorithm statics and record into ``TRACE_LOG`` at trace
time, so same-bucket graphs demonstrably reuse one executable.

In ``bucketing="exact"`` mode the convergence threshold is baked in
statically (``tau * n`` with Python float semantics) — bit-identical to
the legacy ``gsl_lpa`` path, which is what the compatibility wrappers
rely on.  In ``pow2`` mode the threshold is computed from the traced
real vertex count so one executable serves the whole bucket.
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch import lpa_run_batched, split_lp_batched, warm_state_rows
from repro.core.graph import Graph
from repro.core.lpa import lpa_move, lpa_run, neighbors_of
from repro.core.split import min_label_sweep, min_label_wake, split_lp
from repro.engine.bucketing import (
    BatchBucketKey,
    BucketKey,
    batch_index_arrays,
    pad_active,
    pad_graph,
    pad_labels,
)
from repro.engine.cache import TRACE_LOG
from repro.engine.config import EngineConfig
from repro.engine.registry import BackendRun, BatchBackendRun, register_backend
from repro.obs import span
from repro.obs.convergence import batch_profiles, solo_profile


@register_backend("segment")
class SegmentBackend:
    name = "segment"
    supports_batch = True
    supports_partition = True
    supports_fused_partition = True

    def plan_key(self, config: EngineConfig) -> tuple:
        return ()

    def build(self, bucket: BucketKey, config: EngineConfig):
        exact = config.bucketing == "exact"
        tau, max_iterations = config.tau, config.max_iterations
        do_split = config.split in ("lp", "lpp")
        prune = config.split == "lpp"
        shortcut = config.shortcut
        profile = config.profile != "off"
        split_rows = 2 * max_iterations if config.profile == "full" else 0

        def _propagate(graph, n_real, labels0, active0):
            TRACE_LOG.record("segment:propagate")
            return lpa_run(graph, tau=tau, max_iterations=max_iterations,
                           init_labels=labels0,
                           n_real=None if exact else n_real,
                           init_active=active0, profile=profile)

        def _split(graph, labels, n_real):
            TRACE_LOG.record("segment:split")
            return split_lp(graph, labels, prune=prune, shortcut=shortcut,
                            profile_rows=split_rows, n_real=n_real)

        return SimpleNamespace(
            propagate=jax.jit(_propagate),
            split=jax.jit(_split) if do_split else None,
            profile=profile, split_profile_rows=split_rows,
            max_iterations=max_iterations,
        )

    def prepare(self, graph: Graph, bucket: BucketKey,
                config: EngineConfig) -> Graph:
        return pad_graph(graph, bucket)

    def run(self, plan, inputs: Graph, n_real: int,
            init_labels: np.ndarray | None,
            init_active: np.ndarray | None = None) -> BackendRun:
        g = inputs
        labels0 = jnp.asarray(pad_labels(
            np.arange(n_real, dtype=np.int32) if init_labels is None
            else init_labels, n_real, g.n))
        active0 = jnp.asarray(pad_active(init_active, n_real, g.n))

        profiling = getattr(plan, "profile", False)
        with span("engine.propagate") as prop_span:
            out = plan.propagate(g, jnp.int32(n_real), labels0, active0)
            state, pbuf = out if profiling else (out, None)
            labels = jax.block_until_ready(state.labels)
            lpa_iters = int(state.iteration)

        split_iters = 0
        sbuf = None
        with span("engine.split") as split_span:
            if plan.split is not None:
                out = plan.split(g, labels, jnp.int32(n_real))
                st, sbuf = out if plan.split_profile_rows else (out, None)
                labels = jax.block_until_ready(st.labels)
                split_iters = int(st.iterations)

        # profile fetch: one host transfer, after the convergence sync
        profile = solo_profile(pbuf, lpa_iters, sbuf, split_iters,
                               plan.split_profile_rows,
                               int(n_real)) if profiling else None
        return BackendRun(labels=np.asarray(labels),
                          lpa_iterations=lpa_iters,
                          split_iterations=split_iters,
                          edge_slots=g.m_pad,
                          lpa_seconds=prop_span.dur,
                          split_seconds=split_span.dur,
                          profile=profile)

    # --- batched dispatch (GraphBatch disjoint-union packing) ---

    def build_batch(self, bucket: BatchBucketKey, config: EngineConfig):
        tau, max_iterations = config.tau, config.max_iterations
        do_split = config.split in ("lp", "lpp")
        prune = config.split == "lpp"
        shortcut = config.shortcut
        profile = config.profile != "off"
        split_rows = 2 * max_iterations if config.profile == "full" else 0

        def _propagate(graph, sizes, graph_id, voffset, labels0, active0):
            TRACE_LOG.record("segment:batch_propagate")
            return lpa_run_batched(graph, sizes, graph_id, voffset,
                                   labels0, active0,
                                   tau=tau, max_iterations=max_iterations,
                                   profile=profile)

        def _split(graph, sizes, graph_id, voffset, comm):
            TRACE_LOG.record("segment:batch_split")
            return split_lp_batched(graph, sizes, graph_id, voffset, comm,
                                    prune=prune, shortcut=shortcut,
                                    profile_rows=split_rows)

        return SimpleNamespace(
            propagate=jax.jit(_propagate),
            split=jax.jit(_split) if do_split else None,
            profile=profile, split_profile_rows=split_rows,
        )

    def prepare_batch(self, batch, bucket: BatchBucketKey,
                      config: EngineConfig):
        g = pad_graph(batch.graph, BucketKey(bucket.n, bucket.m, bucket.d))
        sizes, graph_id, voffset = batch_index_arrays(batch, bucket.k,
                                                      bucket.n)
        return (g, jnp.asarray(sizes), jnp.asarray(graph_id),
                jnp.asarray(voffset))

    # --- out-of-core partition sweeps (repro.partition.ooc driver) ---
    #
    # One partition's edge window runs as a compact local Graph: rows
    # [0, size) are the owned vertex range, rows [size, n_local) the
    # halo imports (no out-edges, so they can never adopt).  Label
    # *values* stay global vertex ids — the tie-break hash is a function
    # of the raw value — so every sweep takes the full graph's vertex
    # count as a traced ``label_bound`` sentinel; local row counts and
    # edge windows are padded to one uniform per-run shape, so all
    # partitions share a single jitted executable per stage.

    def build_partition(self, config: EngineConfig):
        prune = config.split == "lpp"
        # Unlike the tile backend (where fusion means a real Pallas kernel
        # body, so 'auto' only fuses when one executes), the segment fused
        # sweeps are jnp compositions — one XLA executable instead of two
        # full edge passes per partition visit — and profit on every
        # backend, so 'auto' fuses here.
        fuse = config.fuse_sweeps != "off"

        def _move(graph, labels, cand, seed, bound):
            TRACE_LOG.record("segment:part_move")
            new, _, _ = lpa_move(graph, labels, cand, seed,
                                 label_bound=bound)
            return new

        def _wake(graph, changed):
            TRACE_LOG.record("segment:part_wake")
            return neighbors_of(graph, changed)

        def _split(graph, comm, labels, active, bound):
            TRACE_LOG.record("segment:part_split")
            return min_label_sweep(graph, comm, labels, active, bound,
                                   prune=prune)

        def _split_wake(graph, comm, changed):
            TRACE_LOG.record("segment:part_split_wake")
            return min_label_wake(graph, comm, changed)

        def _fused_move(graph, labels, chg, active, candp, klass, seed,
                        bound):
            TRACE_LOG.record("segment:part_fused_move")
            wake = neighbors_of(graph, chg)
            act = (active & ~candp) | wake
            new, _, _ = lpa_move(graph, labels, act & klass, seed,
                                 label_bound=bound)
            return new, act

        def _fused_split(graph, comm, labels, chg, bound):
            TRACE_LOG.record("segment:part_fused_split")
            if prune:
                sact = min_label_wake(graph, comm, chg)
            else:
                # no-prune split sweeps every row every iteration; rows
                # without a same-community neighbor reduce to their own
                # label, so the all-ones active is the identity on them
                sact = jnp.ones(graph.n, dtype=bool)
            return min_label_sweep(graph, comm, labels, sact, bound,
                                   prune=prune)

        return SimpleNamespace(
            move=jax.jit(_move), wake=jax.jit(_wake),
            split=jax.jit(_split), split_wake=jax.jit(_split_wake),
            fused_move=jax.jit(_fused_move),
            fused_split=jax.jit(_fused_split), fuse=fuse,
        )

    def partition_caps(self, budget: int, d_bucket: int):
        """(max_edges, max_vertices) per partition for a byte budget.

        One resident partition costs ~12 B/edge of locally-remapped
        window plus ~13 B/edge × pow2 padding of device CSR and ~24
        B/row of vertex-indexed locals; halving the budget leaves the
        LRU headroom for per-sweep transient gathers.
        """
        half = max(budget // 2, 1)
        return max(half // 64, 1), max(half // 48, 8)

    def partition_prepare_nbytes(self, shapes) -> int:
        return shapes.m * 13 + (shapes.n_loc + 1) * 4 + shapes.n_loc * 4

    def prepare_partition(self, resident, shapes, config: EngineConfig):
        """Pad a resident slice to the run's uniform local-Graph shape."""
        n_loc, m = shapes.n_loc, shapes.m
        m_w = len(resident.src)
        src = np.zeros(m, np.int32)
        dst = np.zeros(m, np.int32)
        wgt = np.zeros(m, np.float32)
        mask = np.zeros(m, bool)
        src[:m_w] = resident.src
        dst[:m_w] = resident.dst
        wgt[:m_w] = resident.wgt
        mask[:m_w] = True
        row_ptr = np.full(n_loc + 1, m_w, np.int32)
        row_ptr[: resident.size + 1] = resident.row_ptr
        # num_edges is static pytree aux data: it must be the *uniform*
        # padded size, not the per-partition real count, or every distinct
        # window width retraces the sweep jits (validity flows through
        # edge_mask; the sweep kernels never read num_edges)
        g = Graph(n=n_loc, m_pad=m, num_edges=m,
                  row_ptr=jnp.asarray(row_ptr), src=jnp.asarray(src),
                  dst=jnp.asarray(dst), wgt=jnp.asarray(wgt),
                  edge_mask=jnp.asarray(mask),
                  kdeg=jnp.zeros(n_loc, jnp.float32))
        return g, self.partition_prepare_nbytes(shapes)

    def partition_move(self, ops_ns, inputs, labels_loc, cand_owned,
                       seed, bound) -> np.ndarray:
        g = inputs
        cand = np.zeros(g.n, bool)
        cand[: len(cand_owned)] = cand_owned
        return np.asarray(ops_ns.move(g, jnp.asarray(labels_loc),
                                      jnp.asarray(cand),
                                      jnp.int32(seed), bound))

    def partition_wake(self, ops_ns, inputs, changed_loc) -> np.ndarray:
        return np.asarray(ops_ns.wake(inputs, jnp.asarray(changed_loc)))

    def partition_split(self, ops_ns, inputs, comm_loc, labels_loc,
                        active_owned, bound) -> np.ndarray:
        g = inputs
        active = np.zeros(g.n, bool)
        active[: len(active_owned)] = active_owned
        return np.asarray(ops_ns.split(g, jnp.asarray(comm_loc),
                                       jnp.asarray(labels_loc),
                                       jnp.asarray(active), bound))

    def partition_split_wake(self, ops_ns, inputs, comm_loc,
                             changed_loc) -> np.ndarray:
        return np.asarray(ops_ns.split_wake(inputs, jnp.asarray(comm_loc),
                                            jnp.asarray(changed_loc)))

    # Fused partition sweeps (fuse_sweeps != "off"): the ooc driver's
    # lazy-wake loop lets wake + active refresh + move (and split-wake +
    # min-label) run as one XLA executable per partition visit — one pass
    # over the window's edge arrays instead of two, and no host
    # round-trip of the intermediate wake mask.

    def partition_move_fused(self, ops_ns, inputs, labels_loc, changed_loc,
                             active_owned, cand_prev_owned, klass_owned,
                             seed, bound):
        g = inputs

        def pad(col):
            out = np.zeros(g.n, dtype=bool)
            out[: len(col)] = col
            return jnp.asarray(out)

        new, act = ops_ns.fused_move(
            g, jnp.asarray(labels_loc), jnp.asarray(changed_loc),
            pad(active_owned), pad(cand_prev_owned), pad(klass_owned),
            jnp.int32(seed), bound)
        return np.asarray(new), np.asarray(act)

    def partition_split_fused(self, ops_ns, inputs, comm_loc, labels_loc,
                              changed_loc, bound) -> np.ndarray:
        return np.asarray(ops_ns.fused_split(inputs, jnp.asarray(comm_loc),
                                             jnp.asarray(labels_loc),
                                             jnp.asarray(changed_loc),
                                             bound))

    def run_batch(self, plan, inputs,
                  init_labels: np.ndarray | None = None,
                  init_active: np.ndarray | None = None) -> BatchBackendRun:
        g, sizes, graph_id, voffset = inputs
        k1 = sizes.shape[0]
        profiling = getattr(plan, "profile", False)
        labels0, active0 = warm_state_rows(g.n, voffset,
                                           init_labels, init_active)

        with span("engine.propagate") as prop_span:
            out = plan.propagate(g, sizes, graph_id, voffset,
                                 jnp.asarray(labels0), jnp.asarray(active0))
            (labels, iters, pbuf) = out if profiling else (*out, None)
            labels = jax.block_until_ready(labels)

        split_iters = np.zeros(k1, np.int32)
        sbuf = None
        with span("engine.split") as split_span:
            if plan.split is not None:
                out = plan.split(g, sizes, graph_id, voffset, labels)
                (labels, siters, sbuf) = out if plan.split_profile_rows \
                    else (*out, None)
                labels = jax.block_until_ready(labels)
                split_iters = np.asarray(siters)

        profiles = batch_profiles(pbuf, np.asarray(iters), sbuf,
                                  split_iters, plan.split_profile_rows,
                                  np.asarray(sizes)) if profiling else None
        return BatchBackendRun(labels=np.asarray(labels),
                               lpa_iterations=np.asarray(iters),
                               split_iterations=split_iters,
                               edge_slots=g.m_pad,
                               lpa_seconds=prop_span.dur,
                               split_seconds=split_span.dur,
                               profile=profiles)
