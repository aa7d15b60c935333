"""Distributed GSL-LPA: vertex-partitioned label propagation via shard_map.

Layout (DESIGN.md §6): vertices are 1-D partitioned across *all* mesh axes
(pod x data x model flattened); each device owns an equal slice of the
padded neighbor tiles (perfect static load balance).  The global label
vector is replicated; each sub-sweep computes new labels for the local
slice and refreshes the replica with one tiled all-gather — the only
collective in the inner loop (n * 4 bytes per sweep).

Faithful-baseline vs beyond-paper knobs:
  * ``exchange_every=1``  — all-gather after every sub-sweep: bit-identical
    to the single-device semi-synchronous engine (tests enforce equality).
  * ``exchange_every=k>1`` — run k local sub-sweeps on stale remote labels
    between exchanges.  LPA is a chaotic relaxation and tolerates staleness;
    this divides the collective term by k (§Perf hillclimb lever; quality
    measured in ``benchmarks/bench_stale_exchange.py``).
  * the changed mask is never exchanged — it is recovered locally by
    diffing label replicas (§Perf cell-1 iteration 1, -20% wire bytes).

The loop itself is host-driven (one jitted step per iteration) so that the
(labels, active, iteration) state can be checkpointed between iterations —
the fault-tolerance story for multi-hour billion-edge runs.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.parallel.compat import shard_map

from repro.core.graph import Graph, to_padded_neighbors
from repro.core.lpa import _label_hash
from repro.kernels import ops


@partial(jax.tree_util.register_dataclass,
         data_fields=("nbr", "nw", "nmask"),
         meta_fields=("n", "n_pad", "d_max"))
@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """Padded neighbor tiles, row-sharded over the full device grid."""
    n: int        # real vertex count
    n_pad: int    # padded: multiple of (#devices * 8)
    d_max: int
    nbr: jnp.ndarray    # (n_pad, d_max) int32  — sharded on axis 0
    nw: jnp.ndarray     # (n_pad, d_max) float32
    nmask: jnp.ndarray  # (n_pad, d_max) bool


def _all_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def shard_graph(graph: Graph, mesh: Mesh, d_max: int | None = None,
                n_rows: int | None = None) -> ShardedGraph:
    """Host-side build + placement of the sharded tiles.

    ``n_rows``: minimum padded row count — the engine's shape-bucketed
    path passes the vertex bucket here so that every graph in a bucket
    shards to identical tile shapes (one compile per bucket).
    """
    n_dev = int(np.prod(mesh.devices.shape))
    nbr, nw, nmask = to_padded_neighbors(graph, d_max)
    rows = max(nbr.shape[0], n_rows or 0)
    n_pad = ((rows + n_dev * 8 - 1) // (n_dev * 8)) * (n_dev * 8)
    extra = n_pad - nbr.shape[0]
    if extra:
        pad_ids = np.arange(nbr.shape[0], n_pad, dtype=np.int32)
        nbr = np.concatenate(
            [nbr, np.repeat(pad_ids[:, None], nbr.shape[1], 1)], 0)
        nw = np.concatenate([nw, np.zeros((extra, nw.shape[1]), np.float32)], 0)
        nmask = np.concatenate(
            [nmask, np.zeros((extra, nmask.shape[1]), bool)], 0)
    # host arrays go straight to their shards; a jnp.asarray first would
    # stage the whole tile array on one device
    spec = NamedSharding(mesh, P(_all_axes(mesh), None))
    return ShardedGraph(
        n=graph.n, n_pad=n_pad, d_max=nbr.shape[1],
        nbr=jax.device_put(nbr, spec),
        nw=jax.device_put(nw, spec),
        nmask=jax.device_put(nmask, spec),
    )


def graph_input_specs(n_pad: int, d_max: int):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    return dict(
        nbr=jax.ShapeDtypeStruct((n_pad, d_max), jnp.int32),
        nw=jax.ShapeDtypeStruct((n_pad, d_max), jnp.float32),
        nmask=jax.ShapeDtypeStruct((n_pad, d_max), jnp.bool_),
        labels=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        active=jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
        iteration=jax.ShapeDtypeStruct((), jnp.int32),
        n_real=jax.ShapeDtypeStruct((), jnp.int32),
    )


def make_lpa_step(mesh: Mesh, n_pad: int, d_max: int,
                  exchange_every: int = 1, mode: str = "auto",
                  trace_hook=None):
    """Build the jitted distributed LPA iteration.

    One call runs ``exchange_every`` semi-synchronous iterations (2 parity
    sub-sweeps each).  With ``exchange_every=1`` every sub-sweep ends in a
    label all-gather — bit-identical to the single-device engine.  With
    k > 1 only the final sub-sweep all-gathers; earlier sub-sweeps patch the
    device-local slice of the replica (remote labels go stale — the
    beyond-paper collective-term lever).

    Step signature: (nbr, nw, nmask, labels, active, iteration, n_real)
                 -> (labels', active', delta_n)
    ``labels`` replicated (n_pad,); ``active`` row-sharded (n_pad,);
    tiles row-sharded (n_pad, d_max).  ``n_real`` is the unpadded vertex
    count as a traced scalar, so one compiled step serves every graph that
    pads to the same (n_pad, d_max) — the engine's shape-bucket contract.

    ``trace_hook``, when given, is called (with no args) each time the step
    is actually traced — the engine's compile-observability hook.
    """
    axes = _all_axes(mesh)
    n_dev = int(np.prod(mesh.devices.shape))
    n_loc = n_pad // n_dev
    assert n_pad % n_dev == 0
    num_sweeps = 2 * exchange_every

    def step(nbr, nw, nmask, labels, active, iteration, n_real):
        if trace_hook is not None:
            trace_hook()
        row0 = jax.lax.axis_index(axes) * n_loc
        local_ids = row0 + jnp.arange(n_loc, dtype=jnp.int32)
        real_loc = local_ids < n_real
        parity_loc = (_label_hash(local_ids, jnp.int32(-1)) & 1).astype(bool)
        dn_total = jnp.int32(0)

        for s in range(num_sweeps):
            klass = parity_loc if (s % 2) else ~parity_loc
            cand = active & klass & real_loc
            seed = jnp.asarray(num_sweeps * iteration + s, jnp.int32)

            cur = labels[local_ids]
            best_lab, best_w, cur_w = ops.label_argmax(
                labels[nbr], nw, nmask, cur, seed, mode=mode)
            adopt = cand & (best_w > jnp.maximum(cur_w, 0.0))
            new_local = jnp.where(adopt, best_lab, cur)
            changed_local = new_local != cur

            labels_prev = labels
            if s == num_sweeps - 1 or exchange_every == 1:
                # coherent exchange: ONE label all-gather per sub-sweep.
                # (beyond-paper: the changed mask is never exchanged — it is
                # recovered locally as new-replica != old-replica, saving a
                # pred[n] all-gather per sweep, ~20% of collective bytes)
                labels = jax.lax.all_gather(new_local, axes, tiled=True)
            else:
                # stale sub-sweep: patch local slice only (no collective)
                labels = jax.lax.dynamic_update_slice(labels, new_local,
                                                      (row0,))
            changed = labels != labels_prev
            dn_total = dn_total + jax.lax.psum(
                jnp.sum(changed_local.astype(jnp.int32)), axes)
            # pruning: local rows sleep if processed, wake on changed neighbor
            wake = jnp.any(changed[nbr] & nmask, axis=1)
            active = (active & ~cand) | (wake & real_loc)
        return labels, active, dn_total

    in_specs = (P(axes, None), P(axes, None), P(axes, None),  # tiles
                P(), P(axes), P(), P())
    out_specs = (P(), P(axes), P())
    sharded = shard_map(step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)

    tile_sharding = NamedSharding(mesh, P(axes, None))
    vec_sharding = NamedSharding(mesh, P(axes))
    rep = NamedSharding(mesh, P())
    return jax.jit(sharded,
                   in_shardings=(tile_sharding, tile_sharding, tile_sharding,
                                 rep, vec_sharding, rep, rep),
                   out_shardings=(rep, vec_sharding, rep))


def make_split_step(mesh: Mesh, n_pad: int, d_max: int,
                    mode: str = "auto", trace_hook=None):
    """Distributed SL-LP sweep: (tiles..., comm, labels) -> (labels', dn)."""
    axes = _all_axes(mesh)
    n_dev = int(np.prod(mesh.devices.shape))
    n_loc = n_pad // n_dev

    def step(nbr, nw, nmask, comm, labels):
        del nw
        if trace_hook is not None:
            trace_hook()
        row0 = jax.lax.axis_index(axes) * n_loc
        local_ids = row0 + jnp.arange(n_loc, dtype=jnp.int32)
        new_local = ops.min_label(labels[nbr], comm[nbr], nmask,
                                  labels[local_ids], comm[local_ids],
                                  mode=mode)
        changed = new_local != labels[local_ids]
        labels = jax.lax.all_gather(new_local, axes, tiled=True)
        dn = jax.lax.psum(jnp.sum(changed.astype(jnp.int32)), axes)
        return labels, dn

    in_specs = (P(axes, None), P(axes, None), P(axes, None), P(), P())
    out_specs = (P(), P())
    sharded = shard_map(step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)
    tile = NamedSharding(mesh, P(axes, None))
    rep = NamedSharding(mesh, P())
    return jax.jit(sharded, in_shardings=(tile, tile, tile, rep, rep),
                   out_shardings=(rep, rep))


def distributed_gsl_lpa(graph: Graph, mesh: Mesh, tau: float = 0.05,
                        max_iterations: int = 20, exchange_every: int = 1,
                        mode: str = "auto", checkpoint_cb=None):
    """Host-driven distributed GSL-LPA (propagation + SL-LP split).

    ``checkpoint_cb(phase, iteration, labels)`` is invoked after every
    iteration — the FT hook (state is the complete restart point).
    """
    sg = shard_graph(graph, mesh)
    step = make_lpa_step(mesh, sg.n_pad, sg.d_max,
                         exchange_every=exchange_every, mode=mode)
    rep = NamedSharding(mesh, P())
    vec = NamedSharding(mesh, P(_all_axes(mesh)))
    labels = jax.device_put(jnp.arange(sg.n_pad, dtype=jnp.int32), rep)
    active = jax.device_put(
        jnp.arange(sg.n_pad, dtype=jnp.int32) < sg.n, vec)
    it = 0
    while it < max_iterations:
        labels, active, dn = step(sg.nbr, sg.nw, sg.nmask, labels, active,
                                  jnp.int32(it), jnp.int32(sg.n))
        it += 1
        if checkpoint_cb is not None:
            checkpoint_cb("lpa", it, labels)
        # lint: host-sync-ok — documented convergence sync: one scalar
        if int(dn) <= tau * sg.n:
            break

    split = make_split_step(mesh, sg.n_pad, sg.d_max, mode=mode)
    comm = labels
    labels2 = jax.device_put(jnp.arange(sg.n_pad, dtype=jnp.int32), rep)
    sit = 0
    while True:
        labels2, dn = split(sg.nbr, sg.nw, sg.nmask, comm, labels2)
        sit += 1
        if checkpoint_cb is not None:
            checkpoint_cb("split", sit, labels2)
        # lint: host-sync-ok — split fixed-point test, one scalar per round
        if int(dn) == 0:
            break
    return np.asarray(labels2[: sg.n]), it, sit
