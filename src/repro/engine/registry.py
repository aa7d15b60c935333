"""Backend strategy registry + auto-selection policy.

A backend is a stateless strategy object with four hooks:

  * ``plan_key(config)``  — extra hashable statics (placement: mesh shape,
    device count) the compiled plan depends on beyond the algorithm knobs;
  * ``build(bucket, config)`` — construct the plan: jitted executables
    specialised to the bucket shapes (cached by the engine);
  * ``prepare(graph, bucket, config)`` — per-graph host-side prep (padding
    to the bucket, tile construction, device placement);
  * ``run(plan, inputs, n_real, init_labels, init_active)`` — execute,
    returning a :class:`BackendRun`.  ``init_labels`` seeds propagation
    (warm start); ``init_active`` seeds the unprocessed flags (a delta's
    affected frontier) — both optional, None means cold/full.

Backends that set ``supports_batch = True`` additionally implement the
batched trio — ``build_batch`` / ``prepare_batch`` / ``run_batch`` —
executing a whole :class:`repro.core.batch.GraphBatch` in one dispatch
and returning a :class:`BatchBackendRun` with per-graph iteration
counts.  ``run_batch`` takes optional packed (total_vertices,) warm
labels / active seeds (local coordinates; see ``GraphBatch.pack_labels``)
and must treat them bit-identically to per-member solo warm runs.
``Engine.fit_many`` falls back to sequential ``fit`` calls for backends
without the flag (e.g. ``sharded``).

Registration is open: third-party strategies can ``register_backend`` and
be selected by name through ``EngineConfig.backend``.
"""
from __future__ import annotations

from typing import NamedTuple, Protocol

import jax
import numpy as np

from repro.core.graph import _LANE, Graph
from repro.engine.bucketing import (
    BatchBucketKey,
    BucketKey,
    max_degree,
    tile_rows,
    vertex_degree_bucket,
)
from repro.engine.config import EngineConfig
from repro.kernels.tiling import MAX_TILE_DEGREE


class BackendRun(NamedTuple):
    """Raw backend output (labels still padded + uncompacted).

    ``lpa_seconds`` / ``split_seconds`` are the durations of the
    ``engine.propagate`` / ``engine.split`` spans around the two phases.
    ``edge_slots`` is the number of edge cells one gather pass of the
    sweep runs over: the padded edge bucket (segment) or the padded
    tiles' rows x d (tile, sharded), read from shapes the plan holds.
    """
    labels: np.ndarray        # (bucket rows,) int32 — engine slices [:n_real]
    lpa_iterations: int
    split_iterations: int
    edge_slots: int
    lpa_seconds: float
    split_seconds: float
    # ConvergenceProfile when the plan was built with profiling on
    # (EngineConfig.profile != "off"); None otherwise.
    profile: object | None = None


class BatchBackendRun(NamedTuple):
    """Raw batched-backend output (local labels, per-slot iterations)."""
    labels: np.ndarray            # (bucket rows,) int32 local labels
    lpa_iterations: np.ndarray    # (k_bucket + 1,) int32 per slot
    split_iterations: np.ndarray  # (k_bucket + 1,) int32 per slot
    edge_slots: int               # of the packed dispatch (see BackendRun)
    lpa_seconds: float
    split_seconds: float
    # per-slot list of ConvergenceProfile under profiling; None otherwise.
    profile: list | None = None


class Backend(Protocol):
    name: str
    supports_batch: bool

    def plan_key(self, config: EngineConfig) -> tuple: ...

    def build(self, bucket: BucketKey, config: EngineConfig): ...

    def prepare(self, graph: Graph, bucket: BucketKey,
                config: EngineConfig): ...

    def run(self, plan, inputs, n_real: int,
            init_labels: np.ndarray | None,
            init_active: np.ndarray | None = None) -> BackendRun: ...

    def build_batch(self, bucket: BatchBucketKey, config: EngineConfig): ...

    def prepare_batch(self, batch, bucket: BatchBucketKey,
                      config: EngineConfig): ...

    def run_batch(self, plan, inputs,
                  init_labels: np.ndarray | None = None,
                  init_active: np.ndarray | None = None,
                  ) -> BatchBackendRun: ...


_BACKENDS: dict[str, Backend] = {}


def register_backend(name: str):
    def deco(cls):
        _BACKENDS[name] = cls()
        return cls
    return deco


def get_backend(name: str) -> Backend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; "
                         f"registered: {backend_names()}") from None


def backend_names() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


# Auto-selection: the tile path materialises (rows, d) dense neighbor
# tiles — a win on TPU for degree-bounded graphs, a memory loss on skewed
# ones.  Both limits apply to the shapes a tile plan compiles at; cells
# are counted at bucket rows by the tile width lane-rounded to 128, since
# a narrower (rows, d) array may be lane-padded in HBM.  Documented in
# README.md.
# From compiled.memory_analysis() for one TPU v5e (15.75 GB usable): the
# fused tile propagate program at 2^27 cells (2^20 rows x 128) holds
# 1.21 GB of arguments and 5.91 GB of temporaries; at 2^28 cells it needs
# 14.25 GB, which leaves no room for the graph the engine also keeps on
# the device.
_TILE_MAX_CELLS = 1 << 27


def tile_limit_error(n_bucket: int, d_bucket: int) -> str | None:
    """Why the tile path refuses a (vertex bucket, degree bucket), or None."""
    if d_bucket > MAX_TILE_DEGREE:
        return (f"degree bucket {d_bucket} exceeds {MAX_TILE_DEGREE}, the "
                f"widest tile row the kernels compile for")
    lanes = max(d_bucket, _LANE)
    cells = tile_rows(n_bucket) * lanes
    if cells > _TILE_MAX_CELLS:
        return (f"{cells} tile cells ({tile_rows(n_bucket)} rows x "
                f"{lanes} lanes) exceed the {_TILE_MAX_CELLS}-cell limit "
                f"that fits one TPU v5e's HBM")
    return None


def _tile_or_segment(n: int, d_real: int, config: EngineConfig) -> str:
    n_bucket, d_bucket = vertex_degree_bucket(
        n, d_real, bucketing=config.bucketing,
        min_vertex_bucket=config.min_vertex_bucket)
    if jax.default_backend() == "tpu" \
            and tile_limit_error(n_bucket, d_bucket) is None:
        return "tile"
    return "segment"


def choose_backend(graph: Graph, config: EngineConfig) -> str:
    """Pick a backend from graph shape + device topology."""
    if jax.device_count() > 1 or config.mesh is not None:
        return "sharded"
    return _tile_or_segment(graph.n, max_degree(graph), config)


def choose_backend_batch(graphs, config: EngineConfig) -> str:
    """Pick a backend for a batched dispatch (packed-shape thresholds).

    Same policy as :func:`choose_backend` but against the disjoint-union
    shapes: the tile path materialises (total rows, max-member-degree)
    tiles, so the cell budget applies to the packed totals.
    """
    if jax.device_count() > 1 or config.mesh is not None:
        return "sharded"
    return _tile_or_segment(sum(g.n for g in graphs),
                            max(max_degree(g) for g in graphs), config)
