"""Engine configuration and the unified detection result.

``EngineConfig`` is the single knob surface for every execution strategy
(backend) behind :class:`repro.engine.Engine`; ``DetectionResult`` is the
backend-independent return type of ``Engine.fit``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

BACKENDS = ("auto", "segment", "tile", "sharded")
SPLIT_METHODS = ("none", "lp", "lpp", "bfs_host")
BUCKETING = ("pow2", "exact")
WARM_START = ("off", "auto")
FUSE_SWEEPS = ("auto", "on", "off")
PROFILE = ("off", "convergence", "full")
QUALITY = ("off", "basic", "full")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Configuration for :class:`repro.engine.Engine`.

    backend: execution strategy — ``"segment"`` (CSR sort+segment-reduce),
      ``"tile"`` (padded-neighbor tiles / Pallas kernels), ``"sharded"``
      (multi-device shard_map), or ``"auto"`` (chosen per graph from size,
      max degree, and device count).
    tau / max_iterations / split / shortcut: the GSL-LPA algorithm knobs
      (paper Algorithm 3 + Section 4), identical semantics to ``gsl_lpa``.
    bucketing: ``"pow2"`` pads every graph up to power-of-two vertex/edge
      buckets so same-bucket graphs share one compiled executable;
      ``"exact"`` compiles per exact shape (bit-identical to the legacy
      ``gsl_lpa`` path — used by the compatibility wrappers).
    min_vertex_bucket / min_edge_bucket: floors for the pow2 buckets, so a
      stream of small graphs collapses into a single bucket.
    warm_start: ``"auto"`` reuses a previous result's labels as the
      initial assignment whenever a graph's structural fingerprint hits
      the engine's warm-start cache (incremental re-detection on
      evolving graphs; applies to ``fit`` and ``fit_many`` members
      alike); ``"off"`` always starts from singletons.  Explicit
      ``init_labels`` always wins.
    memory_budget: resident edge-byte cap for ``Engine.fit`` (bytes, or
      a string like ``"64MB"``).  A graph whose edge arrays exceed it is
      detected out-of-core: partitioned into contiguous CSR slices swept
      one-resident-at-a-time with halo-label exchange
      (:mod:`repro.partition`) — labels bit-identical to the in-core
      fit.  ``None`` (default) always fits in core.  Per-call override:
      ``fit(graph, memory_budget=...)``.
    patch_churn_threshold: streaming sessions route a delta through the
      in-place CSR splice patch when it touches fewer than this fraction
      of vertices, and through the full vectorized rebuild above it.
      Default from the measured crossover on this container's CPU
      (``bench_streaming_deltas.py --churn-sweep`` reports the sweep).
    warm_cache_size: bound on the per-engine warm-start cache (LRU over
      graph fingerprints) — keeps a long streaming session from growing
      one labels array per graph ever seen.
    compute_metrics: also report modularity and disconnected-community
      fraction on the result (extra device work; off on the hot path).
    exchange_every: sharded backend — label all-gather cadence (1 is
      bit-faithful to single device; >1 trades staleness for bandwidth).
    kernel_mode: tile/sharded kernel dispatch — ``"auto"`` | ``"pallas"``
      | ``"interpret"`` | ``"ref"`` (see kernels/ops.py).
    fuse_sweeps: tile backend — run each sub-sweep's wake + move (and the
      split's wake + min-label) as one fused Pallas dispatch instead of
      two, with the (TILE_B, D) neighbor tiles read once per sweep
      (kernels/fused_sweep.py).  ``"auto"`` fuses exactly when a real
      kernel body executes (kernel_mode pallas/interpret); the jnp oracle
      stays unfused as the parity reference.  ``"on"`` / ``"off"`` force
      it.  Out-of-core partition sweeps fuse on the segment backend too
      under ``"auto"`` (the fused jnp compositions profit on every
      backend); only ``"off"`` disables that.  Labels and iteration
      counts are bit-identical either way (the fused-parity suite
      asserts this).
    mesh: sharded backend — a ``jax.sharding.Mesh``; defaults to one flat
      axis over every visible device.
    profile: per-fit convergence profiling depth.  ``"convergence"``
      captures the propagation phase's per-sub-sweep frontier/changed
      curve; ``"full"`` adds the Split-Last phase.  Counts are recorded
      device-side into a preallocated buffer carried through the sweep
      loop and fetched once after convergence — labels and iteration
      counts stay bit-identical to ``"off"`` (the parity suite asserts
      it), and no host sync enters the hot loop.  The flag is a plan
      static (part of ``algo_key()``), so ``"off"`` keeps today's exact
      executables.  Results surface as ``DetectionResult.profile``.
    quality: per-fit result-quality telemetry depth (``repro.obs.quality``).
      ``"basic"`` reports modularity (one device segment-sum pass over the
      final labels), community count, a community-size summary, and label
      churn vs the warm-start assignment; ``"full"`` adds the
      disconnected-community fraction (reuses ``check_connected``'s cached
      pass — the paper's headline invariant, live).  All of it runs *after*
      convergence on the final labels, so — unlike ``profile`` — the knob is
      NOT part of ``algo_key()``: every quality mode shares the ``"off"``
      executables and labels/iteration counts are bit-identical by
      construction (the parity suite pins it).  Reports land on
      ``DetectionResult.quality`` and in the metrics registry under the
      engine scope's ``quality.*`` names.
    """
    backend: str = "auto"
    tau: float = 0.05
    max_iterations: int = 20
    split: str = "lp"
    shortcut: bool = False
    bucketing: str = "pow2"
    min_vertex_bucket: int = 256
    min_edge_bucket: int = 2048
    warm_start: str = "off"
    warm_cache_size: int = 64
    memory_budget: int | str | None = None
    # Measured: the splice patch ties the rebuild at ~20% churn on this
    # container's CPU (3.7x faster at 2%, 2x slower at 50%) — see
    # bench_streaming_deltas.py's churn sweep, which reports the live
    # crossover so other hardware can recalibrate.
    patch_churn_threshold: float = 0.20
    compute_metrics: bool = False
    exchange_every: int = 1
    kernel_mode: str = "auto"
    fuse_sweeps: str = "auto"
    mesh: Any = None
    profile: str = "off"
    quality: str = "off"

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, "
                             f"got {self.backend!r}")
        if self.split not in SPLIT_METHODS:
            raise ValueError(f"split must be one of {SPLIT_METHODS}, "
                             f"got {self.split!r}")
        if self.bucketing not in BUCKETING:
            raise ValueError(f"bucketing must be one of {BUCKETING}, "
                             f"got {self.bucketing!r}")
        if self.warm_start not in WARM_START:
            raise ValueError(f"warm_start must be one of {WARM_START}, "
                             f"got {self.warm_start!r}")
        if self.fuse_sweeps not in FUSE_SWEEPS:
            raise ValueError(f"fuse_sweeps must be one of {FUSE_SWEEPS}, "
                             f"got {self.fuse_sweeps!r}")
        if self.profile not in PROFILE:
            raise ValueError(f"profile must be one of {PROFILE}, "
                             f"got {self.profile!r}")
        if self.quality not in QUALITY:
            raise ValueError(f"quality must be one of {QUALITY}, "
                             f"got {self.quality!r}")
        if self.exchange_every < 1:
            raise ValueError("exchange_every must be >= 1")
        if self.warm_cache_size < 1:
            raise ValueError("warm_cache_size must be >= 1")
        if self.memory_budget is not None:
            from repro.partition.plan import parse_bytes
            budget = parse_bytes(self.memory_budget)
            if budget < 1:
                raise ValueError("memory_budget must be >= 1 byte")
            object.__setattr__(self, "memory_budget", budget)
        if not 0.0 <= self.patch_churn_threshold <= 1.0:
            raise ValueError("patch_churn_threshold must be in [0, 1]")

    def algo_key(self) -> tuple:
        """The hashable algorithm statics a compiled plan specialises on."""
        return (self.tau, self.max_iterations, self.split, self.shortcut,
                self.exchange_every, self.kernel_mode, self.fuse_sweeps,
                self.profile)


@dataclasses.dataclass
class DetectionResult:
    """Unified result of ``Engine.fit`` — identical shape for all backends."""
    labels: np.ndarray            # (n,) int32, compacted to dense [0, K)
    num_communities: int
    backend: str                  # backend that actually ran
    lpa_iterations: int
    split_iterations: int         # 0 for split in ("none", "bfs_host")
    # Edge cells one gather pass of the sweep ran over: the padded edge
    # bucket (segment) or the padded tiles' rows x d (tile, sharded); a
    # batched member carries its dispatch's count; 0 out of core.
    edge_slots: int
    # phase -> seconds: the durations of the engine.prepare /
    # engine.propagate / engine.split / engine.compact spans
    timings: dict[str, float]
    bucket: tuple                 # (n, m, d) — or (k, n, m, d) when batched
    cache_hit: bool               # compiled plan came from the engine cache
    warm_started: bool            # fit started from caller/previous labels
    modularity: float | None = None
    disconnected_fraction: float | None = None
    # Batched dispatch provenance (``Engine.fit_many``): how many graphs
    # shared the launch and this graph's position in the pack.  Batch-
    # level stage timings appear as ``"prorated_*"`` keys — work-share
    # estimates, not measurements; the real per-stage spans are recorded
    # once at batch level (see ``repro.obs.trace``).
    batch_size: int = 1
    batch_index: int = 0
    # Out-of-core provenance: partition count of the fit (1 = in-core)
    # and the driver's observability counters (peak resident bytes, halo
    # exchange volume, partition loads) when it ran partitioned.
    partitions: int = 1
    ooc: dict | None = None
    # Per-fit convergence profile (``EngineConfig.profile != "off"``):
    # a :class:`repro.obs.ConvergenceProfile` with the per-sub-sweep
    # frontier/changed curves.  None when profiling is off.
    profile: Any = None
    # Per-fit quality report (``EngineConfig.quality != "off"``): a
    # :class:`repro.obs.QualityReport` — modularity, community sizes,
    # churn vs the warm-start assignment, disconnected fraction ("full").
    quality: Any = None
    # Fingerprint of the graph the cached ``disconnected_fraction``
    # was computed against (see ``check_connected``).
    _connected_fp: Any = dataclasses.field(
        default=None, repr=False, compare=False)

    def check_connected(self, graph) -> float:
        """Disconnected-community fraction, computed lazily and cached.

        Lets tests and serving assert the paper's headline invariant
        (``check_connected(graph) == 0.0`` after any split mode) without
        paying for full quality metrics on every fit
        (``compute_metrics=True`` also reports modularity).  ``graph``
        must be the graph this result was fitted on — the result itself
        only holds labels.

        The cache keys on the graph's structural fingerprint: repeated
        calls with the same graph (invariant suites, ``quality="full"``
        telemetry, serving health checks) pay the device pass once, and
        a call with a *different* graph recomputes instead of returning
        a stale fraction.
        """
        from repro.core.graph import graph_fingerprint
        fp = graph_fingerprint(graph)
        if self.disconnected_fraction is None or self._connected_fp != fp:
            import jax.numpy as jnp

            from repro.core.detect import disconnected_fraction
            self.disconnected_fraction = float(
                disconnected_fraction(graph, jnp.asarray(self.labels)))
            self._connected_fp = fp
        return self.disconnected_fraction

    @property
    def lpa_seconds(self) -> float:
        # Solo fits measure "propagation" directly; batched members carry
        # an explicitly-labeled work-share estimate instead.
        return (self.timings.get("propagation", 0.0)
                + self.timings.get("prorated_propagation", 0.0))

    @property
    def split_seconds(self) -> float:
        return (self.timings.get("split", 0.0)
                + self.timings.get("prorated_split", 0.0)
                + self.timings.get("compact", 0.0))

    @property
    def total_seconds(self) -> float:
        return sum(self.timings.values())
