"""Sharded backend: multi-device shard_map execution behind the Engine.

Reuses ``core.distributed``'s step builders but keeps them in the
engine's compile cache: the jitted LPA/split steps are built once per
(shape bucket, mesh, exchange_every) and the host-driven loop replays
them for every graph in the bucket — the real vertex count rides along
as a traced scalar.  With ``exchange_every=1`` (and one device) the
result is bit-identical to the segment and tile backends; with more
devices it matches the single-device engine exactly (enforced by
``tests/test_distributed.py``).

Requesting ``split="lpp"`` is rejected: the distributed split step has no
pruning variant (the all-gather already dominates; see DESIGN.md §6).
"""
from __future__ import annotations

from functools import lru_cache
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.distributed import (
    make_lpa_step,
    make_split_step,
    shard_graph,
)
from repro.core.graph import Graph
from repro.engine.bucketing import (
    BucketKey,
    pad_active,
    pad_labels,
    tile_rows,
)
from repro.engine.cache import TRACE_LOG
from repro.engine.config import EngineConfig
from repro.engine.registry import BackendRun, register_backend
from repro.obs import span


@lru_cache(maxsize=1)
def _default_mesh():
    from repro.launch.mesh import make_flat_mesh
    return make_flat_mesh()


def _resolve_mesh(config: EngineConfig):
    return config.mesh if config.mesh is not None else _default_mesh()


def _shard_rows(bucket_n: int, n_dev: int) -> int:
    per = n_dev * 8
    return ((tile_rows(bucket_n) + per - 1) // per) * per


@register_backend("sharded")
class ShardedBackend:
    name = "sharded"
    # No batched dispatch yet: the shard_map steps gather labels across
    # devices each exchange, and a packed multi-graph layout would need
    # per-shard graph_id bookkeeping (ROADMAP open item).  Engine.fit_many
    # falls back to sequential fits for this backend.
    supports_batch = False

    def plan_key(self, config: EngineConfig) -> tuple:
        # the Mesh itself (hashable: device ids + axis names) — two meshes
        # with equal shape but different devices must not share a plan
        return (_resolve_mesh(config),)

    def build(self, bucket: BucketKey, config: EngineConfig):
        if config.split == "lpp":
            raise ValueError("sharded backend supports split in "
                             "('none', 'lp', 'bfs_host'); use 'lp'")
        mesh = _resolve_mesh(config)
        n_dev = int(np.prod(tuple(mesh.shape.values())))
        rows = _shard_rows(bucket.n, n_dev)
        step = make_lpa_step(
            mesh, rows, bucket.d, exchange_every=config.exchange_every,
            mode=config.kernel_mode,
            trace_hook=lambda: TRACE_LOG.record("sharded:propagate"))
        split = None
        if config.split == "lp":
            split = make_split_step(
                mesh, rows, bucket.d, mode=config.kernel_mode,
                trace_hook=lambda: TRACE_LOG.record("sharded:split"))
        return SimpleNamespace(mesh=mesh, rows=rows, step=step, split=split,
                               tau=config.tau,
                               max_iterations=config.max_iterations)

    def prepare(self, graph: Graph, bucket: BucketKey,
                config: EngineConfig):
        mesh = _resolve_mesh(config)
        n_dev = int(np.prod(tuple(mesh.shape.values())))
        sg = shard_graph(graph, mesh, d_max=bucket.d,
                         n_rows=_shard_rows(bucket.n, n_dev))
        return sg

    def run(self, plan, inputs, n_real: int,
            init_labels: np.ndarray | None,
            init_active: np.ndarray | None = None) -> BackendRun:
        sg = inputs
        mesh = plan.mesh
        axes = tuple(mesh.axis_names)
        rep = NamedSharding(mesh, P())
        vec = NamedSharding(mesh, P(axes))
        labels = jax.device_put(jnp.asarray(pad_labels(
            np.arange(n_real, dtype=np.int32) if init_labels is None
            else init_labels, n_real, plan.rows)), rep)
        active = jax.device_put(
            (jnp.arange(plan.rows, dtype=jnp.int32) < n_real)
            & jnp.asarray(pad_active(init_active, n_real, plan.rows)), vec)
        threshold = int(np.float32(plan.tau) * np.float32(n_real))
        nr = jnp.int32(n_real)

        it = 0
        with span("engine.propagate") as prop_span:
            while it < plan.max_iterations:
                labels, active, dn = plan.step(sg.nbr, sg.nw, sg.nmask,
                                               labels, active,
                                               jnp.int32(it), nr)
                it += 1
                # host-driven convergence loop by design: one scalar readback
                # lint: host-sync-ok — per exchange round (README "sharded")
                if int(dn) <= threshold:
                    break
            labels = jax.block_until_ready(labels)

        sit = 0
        with span("engine.split") as split_span:
            if plan.split is not None:
                comm = labels
                labels = jax.device_put(
                    jnp.arange(plan.rows, dtype=jnp.int32), rep)
                while True:
                    labels, dn = plan.split(sg.nbr, sg.nw, sg.nmask, comm,
                                            labels)
                    sit += 1
                    # lint: host-sync-ok — split fixed-point, one scalar/round
                    if int(dn) == 0:
                        break
                labels = jax.block_until_ready(labels)

        return BackendRun(labels=np.asarray(labels), lpa_iterations=it,
                          split_iterations=sit, edge_slots=sg.nbr.size,
                          lpa_seconds=prop_span.dur,
                          split_seconds=split_span.dur)
