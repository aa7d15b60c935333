"""Tile backend: single-device padded-neighbor path over the Pallas ops.

The propagation loop mirrors ``core.lpa.lpa_run`` sweep-for-sweep (same
parity classes, same per-sweep hash seeds, same adopt rule) but computes
each sweep with ``kernels.ops.label_argmax`` over dense (rows, d_max)
neighbor tiles — the compiled-kernel path on TPU, the jnp oracle
elsewhere.  For integer-valued edge weights the per-community sums are
exact in float32, so the final labels are bit-identical to the segment
backend (the parity suite asserts this); the split phase uses
``ops.min_label`` and matches ``split_lp`` exactly.

Both phases run as single jitted ``lax.while_loop`` executables per shape
bucket; the real vertex count is a traced scalar.

With ``EngineConfig.fuse_sweeps`` resolved on (``ops.resolve_fuse``), the
loop bodies switch to the *lazy-wake* form — the wake reduction for
sub-sweep ``k`` is applied at the start of sub-sweep ``k+1`` from the
carried changed mask, exactly the restructure the out-of-core driver
already uses — so each sub-sweep's wake + move (and the split's wake +
min-label) runs as one fused Pallas dispatch
(``kernels/fused_sweep.py``) with the neighbor tiles read once.  Labels
and iteration counts are bit-identical either way; the fused bodies get
their own TRACE_LOG tags so the trace-audit gate sees them as distinct
contracts.
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch import warm_state_rows
from repro.core.graph import Graph, to_padded_neighbors
from repro.core.lpa import _label_hash
from repro.engine.bucketing import (
    BatchBucketKey,
    BucketKey,
    batch_index_arrays,
    pad_active,
    pad_labels,
    tile_rows,
)
from repro.engine.cache import TRACE_LOG
from repro.engine.config import EngineConfig
from repro.engine.registry import (
    BackendRun,
    BatchBackendRun,
    register_backend,
    tile_limit_error,
)
from repro.kernels import ops
from repro.obs import span
from repro.obs.convergence import batch_profiles, solo_profile


def _check_admitted(n_bucket: int, d_bucket: int) -> None:
    """Refuse a bucket the tile kernels cannot hold, before any trace."""
    why = tile_limit_error(n_bucket, d_bucket)
    if why is not None:
        raise ValueError(f"tile backend refuses this graph: {why}; use "
                         f"backend='segment' (or 'auto')")


def pad_tile_rows(nbr: np.ndarray, nw: np.ndarray, nmask: np.ndarray,
                  rows: int):
    """Grow neighbor tiles to ``rows`` rows: self-pointing ids, zero weight,
    masked out — identical padding semantics to ``to_padded_neighbors``."""
    have = nbr.shape[0]
    if have == rows:
        return nbr, nw, nmask
    if have > rows:
        raise ValueError(f"tiles have {have} rows, bucket wants {rows}")
    extra = rows - have
    pad_ids = np.arange(have, rows, dtype=np.int32)
    nbr = np.concatenate(
        [nbr, np.repeat(pad_ids[:, None], nbr.shape[1], axis=1)], axis=0)
    nw = np.concatenate(
        [nw, np.zeros((extra, nw.shape[1]), np.float32)], axis=0)
    nmask = np.concatenate(
        [nmask, np.zeros((extra, nmask.shape[1]), bool)], axis=0)
    return nbr, nw, nmask


@register_backend("tile")
class TileBackend:
    name = "tile"
    supports_batch = True
    supports_partition = True
    supports_fused_partition = True

    def plan_key(self, config: EngineConfig) -> tuple:
        return ()

    def build(self, bucket: BucketKey, config: EngineConfig):
        _check_admitted(bucket.n, bucket.d)
        rows = tile_rows(bucket.n)
        tau, max_iterations = config.tau, config.max_iterations
        mode = config.kernel_mode
        do_split = config.split in ("lp", "lpp")
        prune = config.split == "lpp"
        shortcut = config.shortcut
        fuse = ops.resolve_fuse(config.fuse_sweeps, config.kernel_mode)
        profile = config.profile != "off"
        split_rows = 2 * max_iterations if config.profile == "full" else 0

        ids = np.arange(rows, dtype=np.int32)

        def _propagate(nbr, nw, nmask, n_real, labels0, active0):
            TRACE_LOG.record("tile:propagate")
            vid = jnp.asarray(ids)
            parity = (_label_hash(vid, jnp.int32(-1)) & 1).astype(bool)
            real = vid < n_real
            threshold = (jnp.float32(tau)
                         * n_real.astype(jnp.float32)).astype(jnp.int32)

            def cond(s):
                _labels, _active, it, dn = s[:4]
                return (dn > threshold) & (it < max_iterations)

            def body(s):
                labels, active, it, _ = s[:4]
                buf = s[4] if profile else None
                dn = jnp.int32(0)
                for sweep in range(2):  # semi-synchronous parity sub-sweeps
                    klass = parity if sweep else ~parity
                    with jax.named_scope("sweep.wake"):
                        cand = active & klass
                    seed = 2 * it + sweep
                    with jax.named_scope("sweep.gather"):
                        nbr_lab = labels[nbr]
                    with jax.named_scope("sweep.reduce"):
                        best_lab, best_w, cur_w = ops.label_argmax(
                            nbr_lab, nw, nmask, labels,
                            jnp.asarray(seed, jnp.int32), mode=mode)
                        adopt = cand & (best_w > jnp.maximum(cur_w, 0.0))
                        new = jnp.where(adopt, best_lab.astype(jnp.int32),
                                        labels)
                    with jax.named_scope("sweep.wake"):
                        changed = new != labels
                        wake = jnp.any(changed[nbr] & nmask, axis=1)
                        active = (active & ~cand) | (wake & real)
                        sc = jnp.sum(changed.astype(jnp.int32))
                        dn = dn + sc
                    labels = new
                    if profile:
                        buf = buf.at[seed].set(jnp.stack(
                            [jnp.sum(cand.astype(jnp.int32)), sc, seed]))
                nxt = (labels, active, it + jnp.int32(1), dn)
                return nxt + (buf,) if profile else nxt

            init = (labels0, active0 & real, jnp.int32(0), jnp.int32(rows))
            if profile:
                init = init + (jnp.full((2 * max_iterations, 3), -1,
                                        jnp.int32),)
                labels, _, it, _, buf = jax.lax.while_loop(cond, body, init)
                return labels, it, buf
            labels, _, it, _ = jax.lax.while_loop(cond, body, init)
            return labels, it

        def _propagate_fused(nbr, nw, nmask, n_real, labels0, active0):
            TRACE_LOG.record("tile:propagate_fused")
            vid = jnp.asarray(ids)
            parity = (_label_hash(vid, jnp.int32(-1)) & 1).astype(bool)
            real = vid < n_real
            threshold = (jnp.float32(tau)
                         * n_real.astype(jnp.float32)).astype(jnp.int32)

            def cond(s):
                _labels, _active, _chg, _candp, it, dn = s[:6]
                return (dn > threshold) & (it < max_iterations)

            def body(s):
                # Lazy wake: chg/candp carry the previous sub-sweep's
                # changed mask and candidate set into the fused kernel,
                # which applies the active refresh before picking this
                # sub-sweep's candidates — one dispatch per sub-sweep.
                labels, active, chg, candp, it, _ = s[:6]
                buf = s[6] if profile else None
                dn = jnp.int32(0)
                for sweep in range(2):  # semi-synchronous parity sub-sweeps
                    klass = parity if sweep else ~parity
                    seed = 2 * it + sweep
                    with jax.named_scope("sweep.gather"):
                        nbr_lab, nbr_chg = labels[nbr], chg[nbr]
                    with jax.named_scope("sweep.reduce"):
                        new, active = ops.fused_move(
                            nbr_lab, nw, nmask, nbr_chg, labels, active,
                            candp, klass, real, jnp.asarray(seed, jnp.int32),
                            mode=mode)
                    with jax.named_scope("sweep.wake"):
                        chg = new != labels
                        # candp is exactly this sub-sweep's candidate set
                        # (refreshed-active & klass) — same counts as the
                        # unfused body's `cand`.
                        candp = active & klass
                        sc = jnp.sum(chg.astype(jnp.int32))
                        dn = dn + sc
                    labels = new
                    if profile:
                        buf = buf.at[seed].set(jnp.stack(
                            [jnp.sum(candp.astype(jnp.int32)), sc, seed]))
                nxt = (labels, active, chg, candp, it + jnp.int32(1), dn)
                return nxt + (buf,) if profile else nxt

            zeros = jnp.zeros(rows, dtype=bool)
            init = (labels0, active0 & real, zeros, zeros, jnp.int32(0),
                    jnp.int32(rows))
            if profile:
                init = init + (jnp.full((2 * max_iterations, 3), -1,
                                        jnp.int32),)
                labels, _, _, _, it, _, buf = jax.lax.while_loop(cond, body,
                                                                 init)
                return labels, it, buf
            labels, _, _, _, it, _ = jax.lax.while_loop(cond, body, init)
            return labels, it

        def _split(nbr, nmask, comm, labels0, n_real):
            TRACE_LOG.record("tile:split")
            with jax.named_scope("sweep.gather"):
                same = (comm[nbr] == comm[:, None]) & nmask
            real = jnp.asarray(ids) < n_real

            def cond(s):
                _labels, _active, _it, dn = s[:4]
                return dn > 0

            def body(s):
                labels, active, it, _ = s[:4]
                buf = s[4] if split_rows else None
                with jax.named_scope("sweep.gather"):
                    nbr_lab, nbr_comm = labels[nbr], comm[nbr]
                with jax.named_scope("sweep.reduce"):
                    new = ops.min_label(nbr_lab, nbr_comm, nmask, labels,
                                        comm, mode=mode)
                    if prune:
                        new = jnp.where(active, new, labels)
                if shortcut:
                    with jax.named_scope("sweep.gather"):
                        new = jnp.minimum(new, new[new])
                with jax.named_scope("sweep.wake"):
                    changed = new != labels
                    dn = jnp.sum(changed.astype(jnp.int32))
                if split_rows:
                    row = jnp.minimum(it, split_rows - 1)
                    buf = buf.at[row].set(jnp.stack(
                        [jnp.sum((active & real).astype(jnp.int32)), dn,
                         it]))
                if prune:
                    with jax.named_scope("sweep.wake"):
                        active = jnp.any(changed[nbr] & same, axis=1)
                nxt = (new, active, it + jnp.int32(1), dn)
                return nxt + (buf,) if split_rows else nxt

            init = (labels0, jnp.ones(rows, dtype=bool), jnp.int32(0),
                    jnp.int32(rows))
            if split_rows:
                init = init + (jnp.full((split_rows, 3), -1, jnp.int32),)
                labels, _, it, _, buf = jax.lax.while_loop(cond, body, init)
                return labels, it, buf
            labels, _, it, _ = jax.lax.while_loop(cond, body, init)
            return labels, it

        def _split_fused(nbr, nmask, comm, labels0, n_real):
            TRACE_LOG.record("tile:split_fused")
            real = jnp.asarray(ids) < n_real

            def cond(s):
                _labels, _chg, _it, dn = s[:4]
                return dn > 0

            def body(s):
                # chg carries last iteration's changed mask (ones on the
                # first: rows with no same-community neighbor reduce to
                # their own label, so the result matches active0 = ones).
                labels, chg, it, _ = s[:4]
                buf = s[4] if split_rows else None
                with jax.named_scope("sweep.gather"):
                    nbr_lab, nbr_comm, nbr_chg = (labels[nbr], comm[nbr],
                                                  chg[nbr])
                with jax.named_scope("sweep.reduce"):
                    new = ops.fused_split(nbr_lab, nbr_comm, nmask, nbr_chg,
                                          labels, comm, prune=prune,
                                          mode=mode)
                if shortcut:
                    with jax.named_scope("sweep.gather"):
                        new = jnp.minimum(new, new[new])
                with jax.named_scope("sweep.wake"):
                    changed = new != labels
                    dn = jnp.sum(changed.astype(jnp.int32))
                if split_rows:
                    # the fused body never materialises the prune
                    # worklist; the wake source (last sweep's changed
                    # rows) is the closest observable frontier proxy
                    row = jnp.minimum(it, split_rows - 1)
                    buf = buf.at[row].set(jnp.stack(
                        [jnp.sum((chg & real).astype(jnp.int32)), dn, it]))
                nxt = (new, changed, it + jnp.int32(1), dn)
                return nxt + (buf,) if split_rows else nxt

            init = (labels0, jnp.ones(rows, dtype=bool), jnp.int32(0),
                    jnp.int32(rows))
            if split_rows:
                init = init + (jnp.full((split_rows, 3), -1, jnp.int32),)
                labels, _, it, _, buf = jax.lax.while_loop(cond, body, init)
                return labels, it, buf
            labels, _, it, _ = jax.lax.while_loop(cond, body, init)
            return labels, it

        return SimpleNamespace(
            rows=rows,
            propagate=jax.jit(_propagate_fused if fuse else _propagate),
            split=(jax.jit(_split_fused if fuse else _split)
                   if do_split else None),
            profile=profile, split_profile_rows=split_rows,
        )

    def prepare(self, graph: Graph, bucket: BucketKey,
                config: EngineConfig):
        nbr, nw, nmask = to_padded_neighbors(graph, d_max=bucket.d)
        nbr, nw, nmask = pad_tile_rows(nbr, nw, nmask, tile_rows(bucket.n))
        return (jnp.asarray(nbr), jnp.asarray(nw), jnp.asarray(nmask))

    def run(self, plan, inputs, n_real: int,
            init_labels: np.ndarray | None,
            init_active: np.ndarray | None = None) -> BackendRun:
        nbr, nw, nmask = inputs
        profiling = getattr(plan, "profile", False)
        labels0 = jnp.asarray(pad_labels(
            np.arange(n_real, dtype=np.int32) if init_labels is None
            else init_labels, n_real, plan.rows))
        active0 = jnp.asarray(pad_active(init_active, n_real, plan.rows))

        with span("engine.propagate") as prop_span:
            out = plan.propagate(nbr, nw, nmask, jnp.int32(n_real),
                                 labels0, active0)
            (labels, it, pbuf) = out if profiling else (*out, None)
            labels = jax.block_until_ready(labels)
            lpa_iters = int(it)

        split_iters = 0
        sbuf = None
        with span("engine.split") as split_span:
            if plan.split is not None:
                roots0 = jnp.arange(plan.rows, dtype=jnp.int32)
                out = plan.split(nbr, nmask, labels, roots0,
                                 jnp.int32(n_real))
                (labels, sit, sbuf) = out if plan.split_profile_rows \
                    else (*out, None)
                labels = jax.block_until_ready(labels)
                split_iters = int(sit)

        # profile fetch: one host transfer, after the convergence sync
        profile = solo_profile(pbuf, lpa_iters, sbuf, split_iters,
                               plan.split_profile_rows,
                               int(n_real)) if profiling else None
        return BackendRun(labels=np.asarray(labels),
                          lpa_iterations=lpa_iters,
                          split_iterations=split_iters,
                          edge_slots=nbr.size,
                          lpa_seconds=prop_span.dur,
                          split_seconds=split_span.dur,
                          profile=profile)

    # --- out-of-core partition sweeps (repro.partition.ooc driver) ---
    #
    # A partition's tiles hold only its *owned* rows (``shapes.rows``
    # high), but neighbor ids index the full local row space (owned +
    # halo), so the per-sweep ``labels_loc`` gather covers halo imports
    # for free.  Label values are global vertex ids — the argmax hash is
    # a function of the raw value, and the kernels' sentinel is INT32_MAX,
    # so no label_bound plumbing is needed on this path.  Tile width is
    # the in-core d bucket: per-row reductions run at identical widths,
    # keeping the float sums bit-identical to the in-core tile fit.

    def build_partition(self, config: EngineConfig):
        mode = config.kernel_mode
        prune = config.split == "lpp"
        fuse = ops.resolve_fuse(config.fuse_sweeps, config.kernel_mode)

        def _move(nbr, nw, nmask, labels, cand, seed):
            TRACE_LOG.record("tile:part_move")
            row_lab = labels[: nbr.shape[0]]
            with jax.named_scope("sweep.gather"):
                nbr_lab = labels[nbr]
            with jax.named_scope("sweep.reduce"):
                best_lab, best_w, cur_w = ops.label_argmax(
                    nbr_lab, nw, nmask, row_lab, seed, mode=mode)
                adopt = cand & (best_w > jnp.maximum(cur_w, 0.0))
                return jnp.where(adopt, best_lab.astype(jnp.int32), row_lab)

        def _wake(nbr, nmask, changed):
            TRACE_LOG.record("tile:part_wake")
            with jax.named_scope("sweep.wake"):
                return jnp.any(changed[nbr] & nmask, axis=1)

        def _split(nbr, nmask, comm, labels, active):
            TRACE_LOG.record("tile:part_split")
            rows = nbr.shape[0]
            with jax.named_scope("sweep.gather"):
                nbr_lab, nbr_comm = labels[nbr], comm[nbr]
            with jax.named_scope("sweep.reduce"):
                new = ops.min_label(nbr_lab, nbr_comm, nmask,
                                    labels[:rows], comm[:rows], mode=mode)
                if prune:
                    new = jnp.where(active, new, labels[:rows])
                return new

        def _split_wake(nbr, nmask, comm, changed):
            TRACE_LOG.record("tile:part_split_wake")
            rows = nbr.shape[0]
            with jax.named_scope("sweep.wake"):
                same = (comm[nbr] == comm[:rows, None]) & nmask
                return jnp.any(changed[nbr] & same, axis=1)

        def _fused_move(nbr, nw, nmask, labels, chg, active, candp, klass,
                        seed):
            TRACE_LOG.record("tile:part_fused_move")
            rows = nbr.shape[0]
            real = jnp.ones(rows, dtype=bool)  # padded rows: nmask/klass off
            with jax.named_scope("sweep.gather"):
                nbr_lab, nbr_chg = labels[nbr], chg[nbr]
            with jax.named_scope("sweep.reduce"):
                return ops.fused_move(nbr_lab, nw, nmask, nbr_chg,
                                      labels[:rows], active, candp, klass,
                                      real, seed, mode=mode)

        def _fused_split(nbr, nmask, comm, labels, chg):
            TRACE_LOG.record("tile:part_fused_split")
            rows = nbr.shape[0]
            with jax.named_scope("sweep.gather"):
                nbr_lab, nbr_comm, nbr_chg = labels[nbr], comm[nbr], chg[nbr]
            with jax.named_scope("sweep.reduce"):
                return ops.fused_split(nbr_lab, nbr_comm, nmask, nbr_chg,
                                       labels[:rows], comm[:rows],
                                       prune=prune, mode=mode)

        return SimpleNamespace(
            move=jax.jit(_move), wake=jax.jit(_wake),
            split=jax.jit(_split), split_wake=jax.jit(_split_wake),
            fused_move=jax.jit(_fused_move),
            fused_split=jax.jit(_fused_split),
            fuse=fuse,
        )

    def partition_caps(self, budget: int, d_bucket: int):
        """(max_edges, max_vertices) for a byte budget: the dense tiles
        cost ~9 B/cell at ``d_bucket`` cells per row, padded ≤ 2x."""
        half = max(budget // 2, 1)
        return max(half // 40, 1), max(half // (18 * max(d_bucket, 1)), 8)

    def partition_prepare_nbytes(self, shapes) -> int:
        return shapes.rows * shapes.d * 9

    def prepare_partition(self, resident, shapes, config: EngineConfig):
        """Dense (rows, d) neighbor tiles of one partition's owned rows.

        Same padding semantics as ``to_padded_neighbors`` (self-pointing
        ids, zero weight, masked out), built vectorized off the local
        window so residency setup is O(window), not a Python row loop.
        """
        rows, d = shapes.rows, shapes.d
        size = resident.size
        row_ptr = resident.row_ptr.astype(np.int64)
        deg = row_ptr[1:] - row_ptr[:-1]
        nbr = np.repeat(np.arange(rows, dtype=np.int32)[:, None], d, axis=1)
        nw = np.zeros((rows, d), np.float32)
        nmask = np.zeros((rows, d), bool)
        if size and len(resident.dst):
            ridx = np.repeat(np.arange(size), deg)
            cidx = np.arange(len(resident.dst)) - np.repeat(row_ptr[:-1], deg)
            nbr[ridx, cidx] = resident.dst
            nw[ridx, cidx] = resident.wgt
            nmask[ridx, cidx] = True
        return ((jnp.asarray(nbr), jnp.asarray(nw), jnp.asarray(nmask)),
                self.partition_prepare_nbytes(shapes))

    def partition_move(self, ops_ns, inputs, labels_loc, cand_owned,
                       seed, bound) -> np.ndarray:
        nbr, nw, nmask = inputs
        cand = np.zeros(nbr.shape[0], bool)
        cand[: len(cand_owned)] = cand_owned
        return np.asarray(ops_ns.move(nbr, nw, nmask,
                                      jnp.asarray(labels_loc),
                                      jnp.asarray(cand), jnp.int32(seed)))

    def partition_wake(self, ops_ns, inputs, changed_loc) -> np.ndarray:
        nbr, _nw, nmask = inputs
        return np.asarray(ops_ns.wake(nbr, nmask, jnp.asarray(changed_loc)))

    def partition_split(self, ops_ns, inputs, comm_loc, labels_loc,
                        active_owned, bound) -> np.ndarray:
        nbr, _nw, nmask = inputs
        active = np.zeros(nbr.shape[0], bool)
        active[: len(active_owned)] = active_owned
        return np.asarray(ops_ns.split(nbr, nmask, jnp.asarray(comm_loc),
                                       jnp.asarray(labels_loc),
                                       jnp.asarray(active)))

    def partition_split_wake(self, ops_ns, inputs, comm_loc,
                             changed_loc) -> np.ndarray:
        nbr, _nw, nmask = inputs
        return np.asarray(ops_ns.split_wake(nbr, nmask,
                                            jnp.asarray(comm_loc),
                                            jnp.asarray(changed_loc)))

    # Fused partition sweeps (fuse_sweeps on): the ooc driver's lazy-wake
    # loop already matches the fused kernel's contract, so wake + move
    # (and split-wake + min-label) collapse into one dispatch per
    # partition visit.  Owned-row state columns pad to the tile height.

    def partition_move_fused(self, ops_ns, inputs, labels_loc, changed_loc,
                             active_owned, cand_prev_owned, klass_owned,
                             seed, bound):
        nbr, nw, nmask = inputs
        rows = nbr.shape[0]

        def pad(col):
            out = np.zeros(rows, dtype=bool)
            out[: len(col)] = col
            return jnp.asarray(out)

        new, act = ops_ns.fused_move(
            nbr, nw, nmask, jnp.asarray(labels_loc),
            jnp.asarray(changed_loc), pad(active_owned),
            pad(cand_prev_owned), pad(klass_owned), jnp.int32(seed))
        return np.asarray(new), np.asarray(act)

    def partition_split_fused(self, ops_ns, inputs, comm_loc, labels_loc,
                              changed_loc, bound) -> np.ndarray:
        nbr, _nw, nmask = inputs
        return np.asarray(ops_ns.fused_split(nbr, nmask,
                                             jnp.asarray(comm_loc),
                                             jnp.asarray(labels_loc),
                                             jnp.asarray(changed_loc)))

    # --- batched dispatch: one tile launch over the packed super-graph.
    # Labels live in per-graph *local* coordinates (the argmax tie-break
    # hashes raw label values); nbr tiles hold global row ids, and the
    # per-slot done/iters state freezes each member exactly where its
    # standalone run would stop.

    def build_batch(self, bucket: BatchBucketKey, config: EngineConfig):
        _check_admitted(bucket.n, bucket.d)
        rows = tile_rows(bucket.n)
        k1 = bucket.k + 1
        tau, max_iterations = config.tau, config.max_iterations
        mode = config.kernel_mode
        do_split = config.split in ("lp", "lpp")
        prune = config.split == "lpp"
        shortcut = config.shortcut
        fuse = ops.resolve_fuse(config.fuse_sweeps, config.kernel_mode)
        profile = config.profile != "off"
        split_rows = 2 * max_iterations if config.profile == "full" else 0

        ids = np.arange(rows, dtype=np.int32)

        def _propagate(nbr, nw, nmask, sizes, graph_id, voffset, n_total,
                       labels0, active0):
            TRACE_LOG.record("tile:batch_propagate")
            vid = jnp.asarray(ids)
            local = vid - voffset
            parity = (_label_hash(local, jnp.int32(-1)) & 1).astype(bool)
            real = vid < n_total
            thr = (jnp.float32(tau)
                   * sizes.astype(jnp.float32)).astype(jnp.int32)
            done0 = sizes <= thr

            def cond(s):
                _labels, _active, it, done, _iters = s[:5]
                return jnp.any(~done) & (it < max_iterations)

            def body(s):
                labels, active, it, done, iters = s[:5]
                buf = s[5] if profile else None
                running = ~done[graph_id]
                dn = jnp.zeros((k1,), jnp.int32)
                for sweep in range(2):  # semi-synchronous parity sub-sweeps
                    klass = parity if sweep else ~parity
                    with jax.named_scope("sweep.wake"):
                        cand = active & klass & running
                    seed = 2 * it + sweep
                    with jax.named_scope("sweep.gather"):
                        nbr_lab = labels[nbr]
                    with jax.named_scope("sweep.reduce"):
                        best_lab, best_w, cur_w = ops.label_argmax(
                            nbr_lab, nw, nmask, labels,
                            jnp.asarray(seed, jnp.int32), mode=mode)
                        adopt = cand & (best_w > jnp.maximum(cur_w, 0.0))
                        new = jnp.where(adopt, best_lab.astype(jnp.int32),
                                        labels)
                    with jax.named_scope("sweep.wake"):
                        changed = new != labels
                        wake = jnp.any(changed[nbr] & nmask, axis=1)
                        active = (active & ~cand) | (wake & real)
                        sc = jax.ops.segment_sum(changed.astype(jnp.int32),
                                                 graph_id, num_segments=k1)
                        dn = dn + sc
                    labels = new
                    if profile:
                        buf = buf.at[seed].set(jnp.stack(
                            [jax.ops.segment_sum(cand.astype(jnp.int32),
                                                 graph_id, num_segments=k1),
                             sc]))
                iters = iters + jnp.where(done, 0, 1)
                nxt = (labels, active, it + jnp.int32(1),
                       done | (dn <= thr), iters)
                return nxt + (buf,) if profile else nxt

            init = (labels0.astype(jnp.int32), active0 & real, jnp.int32(0),
                    done0, jnp.zeros((k1,), jnp.int32))
            if profile:
                init = init + (jnp.full((2 * max_iterations, 2, k1), -1,
                                        jnp.int32),)
                labels, _, _, _, iters, buf = jax.lax.while_loop(cond, body,
                                                                 init)
                return labels, iters, buf
            labels, _, _, _, iters = jax.lax.while_loop(cond, body, init)
            return labels, iters

        def _propagate_fused(nbr, nw, nmask, sizes, graph_id, voffset,
                             n_total, labels0, active0):
            TRACE_LOG.record("tile:batch_propagate_fused")
            vid = jnp.asarray(ids)
            local = vid - voffset
            parity = (_label_hash(local, jnp.int32(-1)) & 1).astype(bool)
            real = vid < n_total
            thr = (jnp.float32(tau)
                   * sizes.astype(jnp.float32)).astype(jnp.int32)
            done0 = sizes <= thr

            def cond(s):
                _labels, _active, _chg, _candp, it, done, _iters = s[:7]
                return jnp.any(~done) & (it < max_iterations)

            def body(s):
                # Lazy wake (see the solo fused body); done graphs keep
                # running=False folded into the candidate class column.
                labels, active, chg, candp, it, done, iters = s[:7]
                buf = s[7] if profile else None
                running = ~done[graph_id]
                dn = jnp.zeros((k1,), jnp.int32)
                for sweep in range(2):  # semi-synchronous parity sub-sweeps
                    klass = parity if sweep else ~parity
                    seed = 2 * it + sweep
                    with jax.named_scope("sweep.gather"):
                        nbr_lab, nbr_chg = labels[nbr], chg[nbr]
                    with jax.named_scope("sweep.reduce"):
                        new, active = ops.fused_move(
                            nbr_lab, nw, nmask, nbr_chg, labels, active,
                            candp, klass & running, real,
                            jnp.asarray(seed, jnp.int32), mode=mode)
                    with jax.named_scope("sweep.wake"):
                        chg = new != labels
                        candp = active & klass & running
                        sc = jax.ops.segment_sum(chg.astype(jnp.int32),
                                                 graph_id, num_segments=k1)
                        dn = dn + sc
                    labels = new
                    if profile:
                        # candp is exactly this sub-sweep's candidate set
                        buf = buf.at[seed].set(jnp.stack(
                            [jax.ops.segment_sum(candp.astype(jnp.int32),
                                                 graph_id, num_segments=k1),
                             sc]))
                iters = iters + jnp.where(done, 0, 1)
                nxt = (labels, active, chg, candp, it + jnp.int32(1),
                       done | (dn <= thr), iters)
                return nxt + (buf,) if profile else nxt

            zeros = jnp.zeros(rows, dtype=bool)
            init = (labels0.astype(jnp.int32), active0 & real, zeros, zeros,
                    jnp.int32(0), done0, jnp.zeros((k1,), jnp.int32))
            if profile:
                init = init + (jnp.full((2 * max_iterations, 2, k1), -1,
                                        jnp.int32),)
                labels, _, _, _, _, _, iters, buf = jax.lax.while_loop(
                    cond, body, init)
                return labels, iters, buf
            labels, _, _, _, _, _, iters = jax.lax.while_loop(cond, body,
                                                              init)
            return labels, iters

        def _split(nbr, nmask, sizes, graph_id, voffset, comm):
            TRACE_LOG.record("tile:batch_split")
            vid = jnp.asarray(ids)
            local = vid - voffset
            with jax.named_scope("sweep.gather"):
                same = (comm[nbr] == comm[:, None]) & nmask
            done0 = sizes == 0

            def cond(s):
                _labels, _active, done, _iters = s[:4]
                return jnp.any(~done)

            def body(s):
                labels, active, done, iters = s[:4]
                buf = s[4] if split_rows else None
                with jax.named_scope("sweep.gather"):
                    nbr_lab, nbr_comm = labels[nbr], comm[nbr]
                with jax.named_scope("sweep.reduce"):
                    new = ops.min_label(nbr_lab, nbr_comm, nmask, labels,
                                        comm, mode=mode)
                    if prune:
                        new = jnp.where(active, new, labels)
                if shortcut:
                    with jax.named_scope("sweep.gather"):
                        new = jnp.minimum(new, new[new + voffset])
                with jax.named_scope("sweep.wake"):
                    changed = new != labels
                    dn = jax.ops.segment_sum(changed.astype(jnp.int32),
                                             graph_id, num_segments=k1)
                if split_rows:
                    # iters.max() is the global sweep index: a not-yet-done
                    # slot increments every sweep, so its count equals the
                    # body-execution count.  Rows past the cap overwrite
                    # the last row (flagged truncated at fetch time).
                    row = jnp.minimum(iters.max(), split_rows - 1)
                    buf = buf.at[row].set(jnp.stack(
                        [jax.ops.segment_sum(active.astype(jnp.int32),
                                             graph_id, num_segments=k1),
                         dn]))
                if prune:
                    with jax.named_scope("sweep.wake"):
                        active = jnp.any(changed[nbr] & same, axis=1)
                iters = iters + jnp.where(done, 0, 1)
                nxt = (new, active, done | (dn == 0), iters)
                return nxt + (buf,) if split_rows else nxt

            init = (local, jnp.ones(rows, dtype=bool), done0,
                    jnp.zeros((k1,), jnp.int32))
            if split_rows:
                init = init + (jnp.full((split_rows, 2, k1), -1,
                                        jnp.int32),)
                labels, _, _, iters, buf = jax.lax.while_loop(cond, body,
                                                              init)
                return labels, iters, buf
            labels, _, _, iters = jax.lax.while_loop(cond, body, init)
            return labels, iters

        def _split_fused(nbr, nmask, sizes, graph_id, voffset, comm):
            TRACE_LOG.record("tile:batch_split_fused")
            vid = jnp.asarray(ids)
            local = vid - voffset
            done0 = sizes == 0

            def cond(s):
                _labels, _chg, done, _iters = s[:4]
                return jnp.any(~done)

            def body(s):
                labels, chg, done, iters = s[:4]
                buf = s[4] if split_rows else None
                with jax.named_scope("sweep.gather"):
                    nbr_lab, nbr_comm, nbr_chg = (labels[nbr], comm[nbr],
                                                  chg[nbr])
                with jax.named_scope("sweep.reduce"):
                    new = ops.fused_split(nbr_lab, nbr_comm, nmask, nbr_chg,
                                          labels, comm, prune=prune,
                                          mode=mode)
                if shortcut:
                    with jax.named_scope("sweep.gather"):
                        new = jnp.minimum(new, new[new + voffset])
                with jax.named_scope("sweep.wake"):
                    changed = new != labels
                    dn = jax.ops.segment_sum(changed.astype(jnp.int32),
                                             graph_id, num_segments=k1)
                if split_rows:
                    # Fused bodies fold the prune worklist into the kernel,
                    # so last sweep's changed set stands in as the frontier.
                    row = jnp.minimum(iters.max(), split_rows - 1)
                    buf = buf.at[row].set(jnp.stack(
                        [jax.ops.segment_sum(chg.astype(jnp.int32),
                                             graph_id, num_segments=k1),
                         dn]))
                iters = iters + jnp.where(done, 0, 1)
                nxt = (new, changed, done | (dn == 0), iters)
                return nxt + (buf,) if split_rows else nxt

            init = (local, jnp.ones(rows, dtype=bool), done0,
                    jnp.zeros((k1,), jnp.int32))
            if split_rows:
                init = init + (jnp.full((split_rows, 2, k1), -1,
                                        jnp.int32),)
                labels, _, _, iters, buf = jax.lax.while_loop(cond, body,
                                                              init)
                return labels, iters, buf
            labels, _, _, iters = jax.lax.while_loop(cond, body, init)
            return labels, iters

        return SimpleNamespace(
            rows=rows,
            propagate=jax.jit(_propagate_fused if fuse else _propagate),
            split=(jax.jit(_split_fused if fuse else _split)
                   if do_split else None),
            profile=profile,
            split_profile_rows=split_rows if do_split else 0,
        )

    def prepare_batch(self, batch, bucket: BatchBucketKey,
                      config: EngineConfig):
        rows = tile_rows(bucket.n)
        nbr, nw, nmask = to_padded_neighbors(batch.graph, d_max=bucket.d)
        nbr, nw, nmask = pad_tile_rows(nbr, nw, nmask, rows)
        sizes, graph_id, voffset = batch_index_arrays(batch, bucket.k, rows)
        return (jnp.asarray(nbr), jnp.asarray(nw), jnp.asarray(nmask),
                jnp.asarray(sizes), jnp.asarray(graph_id),
                jnp.asarray(voffset), jnp.int32(batch.total_vertices))

    def run_batch(self, plan, inputs,
                  init_labels: np.ndarray | None = None,
                  init_active: np.ndarray | None = None) -> BatchBackendRun:
        nbr, nw, nmask, sizes, graph_id, voffset, n_total = inputs
        k1 = sizes.shape[0]
        labels0, active0 = warm_state_rows(plan.rows, voffset,
                                           init_labels, init_active)
        profiling = getattr(plan, "profile", False)

        with span("engine.propagate") as prop_span:
            out = plan.propagate(nbr, nw, nmask, sizes, graph_id,
                                 voffset, n_total,
                                 jnp.asarray(labels0),
                                 jnp.asarray(active0))
            (labels, iters, pbuf) = out if profiling else (*out, None)
            labels = jax.block_until_ready(labels)

        split_iters = np.zeros(k1, np.int32)
        sbuf = None
        with span("engine.split") as split_span:
            if plan.split is not None:
                out = plan.split(nbr, nmask, sizes, graph_id, voffset,
                                 labels)
                (labels, siters, sbuf) = (out if plan.split_profile_rows
                                          else (*out, None))
                labels = jax.block_until_ready(labels)
                split_iters = np.asarray(siters)

        profiles = None
        if profiling:
            profiles = batch_profiles(pbuf, np.asarray(iters), sbuf,
                                      split_iters,
                                      plan.split_profile_rows,
                                      np.asarray(sizes))

        return BatchBackendRun(labels=np.asarray(labels),
                               lpa_iterations=np.asarray(iters),
                               split_iterations=split_iters,
                               edge_slots=nbr.size,
                               lpa_seconds=prop_span.dur,
                               split_seconds=split_span.dur,
                               profile=profiles)
