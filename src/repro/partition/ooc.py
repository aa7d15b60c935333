"""Out-of-core partitioned GSL-LPA: detect graphs bigger than RAM.

The driver sweeps a :class:`~repro.partition.plan.PartitionPlan` one
resident partition at a time through a backend's partition-sweep kernels
(``segment`` / ``tile`` — see their ``build_partition`` hooks), keeping
only O(n) vertex-indexed state resident (the shared global label array,
active flags, ``row_ptr``) while the O(m) edge windows stream under a
hard byte budget (:class:`~repro.partition.slices.MemoryLedger`).

**Bit-parity with the in-core fit is by construction, not by luck.**
Every in-core sweep — ``lpa_move`` sub-sweeps and the §3.3 split's
min-label sweeps — is *synchronous*: new labels are a pure function of
the pre-sweep label snapshot.  So processing partitions sequentially
against that same snapshot (halo labels gathered from the shared global
array) and double-buffering the results reproduces the in-core sweep
exactly, whatever the partition count; the per-partition split phase
converges to one label per (community x component) through the outer
fixed-point loop, which *is* the cross-partition label-unification pass.
Three details make it exact rather than approximate:

* pruning reactivation is evaluated **lazily**: a sweep's wake-up mask
  depends on the sweep's final changed flags, which are only complete
  after the last partition — so each partition refreshes its own rows'
  active flags at the start of its *next* sweep, from its own edge
  window (the rule reads each vertex's own neighborhood, so no second
  edge pass is needed);
* the Shiloach-Vishkin pointer shortcut gathers at arbitrary label
  values, so it runs as a global O(n) vertex pass after each assembled
  sweep — the exact position it occupies in the in-core sweep body;
* convergence thresholds replicate the in-core float semantics per
  (backend, bucketing) combination.
"""
from __future__ import annotations

import dataclasses
import os
import time

import jax.numpy as jnp
import numpy as np

from repro.core.lpa import _label_hash
from repro.engine.cache import trace_context
from repro.engine.config import EngineConfig
from repro.obs import REGISTRY, span
from repro.obs.convergence import ConvergenceProfile, phase_from_rows
from repro.partition.plan import (
    PartitionPlan,
    attach_halos,
    parse_bytes,
    plan_partitions,
)
from repro.partition.slices import (
    HaloLabelCache,
    InMemorySource,
    MemoryLedger,
    PartitionShapes,
    SliceLoader,
    StoreEntrySource,
)

# In-core residency of one directed edge slot: src + dst + wgt + mask.
IN_CORE_EDGE_BYTES = 13

# Shared registry scope for all out-of-core fits in this process: ooc
# infrastructure counters are cumulative across fits (like the engine's
# warm-cache counters), so one scope serves every ``fit_out_of_core``
# call instead of leaking a labeled child scope per fit.
_OOC = REGISTRY.scope("ooc")
_M_FITS = _OOC.counter("fits")
_M_EXCHANGE = _OOC.counter("exchange_bytes")


@dataclasses.dataclass
class OocRun:
    """Raw out-of-core run result + observability counters."""
    labels: np.ndarray            # (n,) int32 — uncompacted global labels
    backend: str
    lpa_iterations: int
    split_iterations: int
    lpa_seconds: float
    split_seconds: float
    plan_seconds: float           # partitioning + halo scan + first prep
    num_partitions: int
    peak_resident_bytes: int
    budget: int
    halo_vertices: int            # total halo rows across partitions
    exchange_bytes: int           # label bytes gathered/scattered, all sweeps
    partition_loads: int          # slice loads actually paid (LRU misses)
    cache_hit: bool               # sweep kernels came from the engine cache
    plan_stats: dict
    fused: bool = False           # partition sweeps ran the fused kernels
    prefetches: int = 0           # windows staged on the prefetch worker
    prefetch_hits: int = 0        # loads served by a staged window
    halo_cache_bytes_saved: int = 0  # gather bytes skipped via label cache
    halo_cache_hits: int = 0      # partition visits with zero re-upload
    profile: object | None = None  # ConvergenceProfile when cfg.profile on

    def stats(self) -> dict:
        return {
            "backend": self.backend, "partitions": self.num_partitions,
            "budget": self.budget,
            "peak_resident_bytes": self.peak_resident_bytes,
            "halo_vertices": self.halo_vertices,
            "exchange_bytes": self.exchange_bytes,
            "partition_loads": self.partition_loads,
            "lpa_iterations": self.lpa_iterations,
            "split_iterations": self.split_iterations,
            "fused": self.fused,
            "prefetches": self.prefetches,
            "prefetch_hits": self.prefetch_hits,
            "halo_cache_bytes_saved": self.halo_cache_bytes_saved,
            "halo_cache_hits": self.halo_cache_hits,
            **{f"plan_{k}": v for k, v in self.plan_stats.items()},
        }


def open_source(graph, **load_kwargs):
    """Graph -> :class:`InMemorySource`; path -> store-backed windows.

    Paths route through :func:`repro.io.store.open_graph`, which ingests
    on first contact and afterwards serves zero-copy windows off the
    store's single mmap — the only path that truly never materializes
    the edge arrays.
    """
    from repro.core.graph import Graph
    if isinstance(graph, Graph):
        return InMemorySource(graph)
    if isinstance(graph, str) or hasattr(graph, "__fspath__"):
        from repro.io.store import open_graph
        return StoreEntrySource(open_graph(graph, **load_kwargs))
    raise TypeError(f"expected a Graph or a graph-file path, "
                    f"got {type(graph).__name__}")


def in_core_edge_bytes(source) -> int:
    """Edge-array bytes an in-core fit would hold resident."""
    return int(source.m_pad) * IN_CORE_EDGE_BYTES


def choose_partition_backend(config: EngineConfig, d_bucket: int,
                             n: int) -> str:
    """OOC flavor of the engine's auto policy (sharded never applies:
    the driver is a single-device streaming loop)."""
    import jax

    from repro.engine.registry import tile_limit_error
    if (jax.default_backend() == "tpu"
            and tile_limit_error(n, d_bucket) is None):
        return "tile"
    return "segment"


def _host_parity(n: int) -> np.ndarray:
    """The semi-synchronous sub-sweep classes, via the real device hash
    (zero drift risk vs. a host reimplementation)."""
    return np.asarray((_label_hash(jnp.arange(n, dtype=jnp.int32),
                                   jnp.int32(-1)) & 1).astype(bool))


def _host_threshold(n: int, tau: float, backend: str,
                    bucketing: str) -> int:
    """Replicate the in-core convergence threshold bit-for-bit.

    The segment backend in ``exact`` bucketing bakes ``tau * n`` in with
    Python float semantics; every other combination computes
    ``float32(tau) * float32(n)`` from the traced real vertex count.
    Both truncate toward zero on the int cast.
    """
    if backend == "segment" and bucketing == "exact":
        return int(np.int32(tau * n))
    return int(np.int32(np.float32(tau) * np.float32(n)))


def _shapes_for(plan: PartitionPlan, bucketing: str) -> PartitionShapes:
    from repro.core.graph import _LANE, _round_up, next_pow2, tile_width
    rows = next_pow2(plan.max_part_size, 8)
    n_loc = max(next_pow2(plan.max_n_local, 8), rows)
    m = max(_round_up(next_pow2(plan.max_part_edges), _LANE), _LANE)
    d = tile_width(plan.d_max, exact=bucketing == "exact")
    return PartitionShapes(n_loc=n_loc, m=m, rows=rows, d=d)


def fit_out_of_core(source, config: EngineConfig | None = None, *,
                    memory_budget, backend: str | None = None,
                    cache=None, num_partitions: int | None = None,
                    init_labels: np.ndarray | None = None,
                    init_active: np.ndarray | None = None,
                    prefetch: bool | None = None,
                    halo_cache: bool = True) -> OocRun:
    """Detect communities with edge residency capped at ``memory_budget``.

    ``source``: an array source from :func:`open_source`.  ``config``:
    the usual :class:`EngineConfig` algorithm knobs (``split`` must be
    device-side — ``bfs_host`` needs the full adjacency in host memory).
    ``cache``: optional engine :class:`CompileCache` for the partition
    sweep kernels.  ``num_partitions`` overrides the budget-derived
    partition count (benchmarks); the byte budget stays enforced either
    way.  Warm starts (``init_labels`` / ``init_active``) behave exactly
    like ``Engine.fit``'s — they are O(n) vertex state, which the
    semi-external model keeps resident anyway.

    ``prefetch`` stages partition ``k+1``'s window + device prep on a
    worker thread while partition ``k`` sweeps (ledger-reserved before
    the thread starts); the ``None`` default enables it exactly when a
    second CPU exists for the worker to overlap on.  ``halo_cache``
    (default on) keeps device-resident local label views per partition
    and re-uploads only changed entries on re-visits.  Both degrade to
    the serial path under budget pressure, and neither changes a single
    label — the parity suite runs with them toggled both ways.

    Returns an :class:`OocRun`; ``labels`` are bit-identical to the
    in-core ``Engine.fit`` labels for the same (backend, config).
    """
    cfg = config if config is not None else EngineConfig()
    if cfg.split == "bfs_host":
        raise ValueError(
            "split='bfs_host' walks the full adjacency in host memory and "
            "cannot run out-of-core; use split='lp' or 'lpp'")
    budget = parse_bytes(memory_budget)
    if prefetch is None:
        cores = (len(os.sched_getaffinity(0))
                 if hasattr(os, "sched_getaffinity")
                 else (os.cpu_count() or 1))
        prefetch = cores > 1

    t0 = time.perf_counter()
    row_ptr = np.asarray(source.row_ptr())
    n = int(source.n)

    from repro.core.graph import tile_width
    degrees = row_ptr[1:] - row_ptr[:-1]
    d_real = int(degrees.max()) if n else 1
    d_bucket = tile_width(d_real)

    name = backend or cfg.backend
    if name == "auto":
        name = choose_partition_backend(cfg, d_bucket, n)
    import repro.engine.backends  # noqa: F401  (registers built-ins)
    from repro.engine.registry import get_backend
    be = get_backend(name)
    if not getattr(be, "supports_partition", False):
        raise ValueError(f"backend {name!r} has no partition sweeps; "
                         "out-of-core fits support segment and tile")

    with span("ooc.plan", n=n, backend=name) as sp_plan:
        if num_partitions is not None:
            plan = plan_partitions(row_ptr, num_partitions=num_partitions)
        else:
            max_edges, max_vertices = be.partition_caps(budget, d_bucket)
            plan = plan_partitions(row_ptr, max_edges=max_edges,
                                   max_vertices=max_vertices)
        plan = attach_halos(plan,
                            lambda lo, hi: source.window("dst", lo, hi))
        shapes = _shapes_for(plan, cfg.bucketing)

        if cache is not None:
            key = ("partition", name, cfg.algo_key(), be.plan_key(cfg))
            sweeps, cache_hit = cache.get_or_build(
                key, lambda: be.build_partition(cfg))
        else:
            sweeps, cache_hit = be.build_partition(cfg), False
        sp_plan.set(partitions=plan.num_partitions,
                    halo_vertices=plan.halo_vertices, cache_hit=cache_hit)

    fused = bool(getattr(be, "supports_fused_partition", False)
                 and getattr(sweeps, "fuse", False))

    ledger = MemoryLedger(budget, scope=_OOC)
    loader = SliceLoader(source, plan, ledger,
                         prefetch=prefetch and plan.num_partitions > 1,
                         scope=_OOC)
    prepare = _Prepare(be, shapes, cfg)

    # Device-resident halo-label caches, one per global array so epochs
    # never mix (labels evolve per sub-sweep; comm is frozen during the
    # split; slab evolves per split iteration).  Registered as spillers:
    # window loads reclaim cache bytes before the ledger would fail.
    caches: list[HaloLabelCache] = []
    lab_cache = comm_cache = slab_cache = None
    if halo_cache:
        lab_cache = HaloLabelCache(ledger, n, shapes.n_loc, "labels")
        comm_cache = HaloLabelCache(ledger, n, shapes.n_loc, "comm")
        slab_cache = HaloLabelCache(ledger, n, shapes.n_loc, "slab")
        caches = [lab_cache, comm_cache, slab_cache]
        loader.spillers.extend(c.spill for c in caches)

    # --- resident O(n) vertex state (the semi-external model's half) ---
    labels = (np.arange(n, dtype=np.int32) if init_labels is None
              else np.asarray(init_labels, dtype=np.int32).copy())
    active = (np.ones(n, dtype=bool) if init_active is None
              else np.asarray(init_active, dtype=bool).copy())
    parity = _host_parity(n)
    threshold = _host_threshold(n, cfg.tau, name, cfg.bucketing)
    bound = jnp.int32(n)
    exchange = Exchange(shapes)
    # trace-audit attribution: every partition sweep dispatch of this fit
    # lands in one (backend, partition-shape-bucket) context
    part_ctx = ("partition", shapes.n_loc, shapes.m, shapes.rows, shapes.d)
    t_plan = time.perf_counter() - t0

    def gather(cache, arr, res):
        """Cached local view when possible, plain host gather otherwise."""
        if cache is not None:
            out = cache.gather(res.part.index, res.local_ids, arr)
            if out is not None:
                return out
        return exchange.gather(arr, res.local_ids)

    def visit(i):
        """Load partition ``i`` and stage ``i+1`` behind it."""
        res = loader.load(i, prepare)
        loader.prefetch((i + 1) % plan.num_partitions, prepare, keep=i)
        return res

    zeros_loc = np.zeros(shapes.n_loc, dtype=bool)
    ones_loc = np.ones(shapes.n_loc, dtype=bool)

    # --- propagation: Algorithm 3 lines 1-6, partitioned ---
    # Profile rows accumulate host-side at the driver's existing sync
    # points (the per-sub-sweep changed reductions already drive the
    # convergence loop), so cfg.profile adds zero new host syncs here.
    do_profile = cfg.profile != "off"
    prop_rows: list[tuple[int, int, int]] = []
    split_rows: list[tuple[int, int, int]] = []
    t0 = time.perf_counter()
    changed_prev: np.ndarray | None = None
    klass_prev: np.ndarray | None = None
    it, delta = 0, n
    with trace_context(name, part_ctx), \
            span("ooc.propagation", backend=name) as sp_lpa:
        while delta > threshold and it < cfg.max_iterations:
            delta = 0
            for sweep in (0, 1):
                klass = parity if sweep else ~parity
                seed = 2 * it + sweep
                labels_next = labels.copy()
                changed_next = np.zeros(n, dtype=bool)
                sweep_delta = 0
                cand_count = 0
                for i in range(plan.num_partitions):
                    res = visit(i)
                    part, rng = res.part, slice(res.part.lo, res.part.hi)
                    loc = res.local_ids
                    lab_loc = gather(lab_cache, labels, res)
                    if fused:
                        # one dispatch: lazy active refresh + candidate
                        # pick + move (kernels/fused_sweep.py)
                        if changed_prev is not None:
                            chg_loc = exchange.gather(changed_prev, loc)
                            candp = active[rng] & klass_prev[rng]
                        else:
                            chg_loc = zeros_loc
                            candp = np.zeros(part.size, dtype=bool)
                        new, act = be.partition_move_fused(
                            sweeps, res.inputs, lab_loc, chg_loc,
                            active[rng], candp, klass[rng], seed, bound)
                        active[rng] = act[: part.size]
                        new = new[: part.size]
                        if do_profile:
                            # the returned act is post-wake, pre-move —
                            # act & klass is the exact candidate set the
                            # fused kernel swept (same count as unfused)
                            cand_count += int(
                                (active[rng] & klass[rng]).sum())
                    else:
                        if changed_prev is not None:
                            # lazy pruning update: finish the previous
                            # sweep's active refresh for this partition
                            wake = be.partition_wake(
                                sweeps, res.inputs,
                                exchange.gather(changed_prev,
                                                loc))[: part.size]
                            was_cand = active[rng] & klass_prev[rng]
                            active[rng] = (active[rng] & ~was_cand) | wake
                        cand = active[rng] & klass[rng]
                        if do_profile:
                            cand_count += int(cand.sum())
                        new = be.partition_move(
                            sweeps, res.inputs, lab_loc,
                            cand, seed, bound)[: part.size]
                    exchange.scatter(labels_next, rng, new)
                    ch = new != labels[rng]
                    changed_next[rng] = ch
                    sweep_delta += int(ch.sum())
                delta += sweep_delta
                if do_profile:
                    prop_rows.append((seed, cand_count, sweep_delta))
                labels = labels_next
                if lab_cache is not None:
                    lab_cache.advance(changed_next)
                changed_prev, klass_prev = changed_next, klass
            it += 1
    lpa_iterations = it
    sp_lpa.set(iterations=it, partitions=plan.num_partitions)
    t_lpa = time.perf_counter() - t0

    # --- §3.3 split phase, per-partition with cross-partition
    # unification via the shared global label array ---
    t0 = time.perf_counter()
    split_iterations = 0
    if cfg.split in ("lp", "lpp"):
        prune = cfg.split == "lpp"
        comm = labels                      # frozen community assignment
        slab = np.arange(n, dtype=np.int32)
        sactive = np.ones(n, dtype=bool)
        changed_prev = None
        delta = 1
        with trace_context(name, part_ctx), \
                span("ooc.split", backend=name) as sp_split:
            while delta > 0:
                # frontier proxy: the split worklist is not materialized
                # host-side (LP sweeps everyone; LPP wakes lazily inside
                # partition visits), so record n for the first sweep and
                # the previous sweep's changed count after — the same
                # proxy the fused in-core split profile uses.
                active_proxy = n if changed_prev is None else delta
                slab_next = slab.copy()
                for i in range(plan.num_partitions):
                    res = visit(i)
                    part, rng = res.part, slice(res.part.lo, res.part.hi)
                    loc = res.local_ids
                    comm_loc = gather(comm_cache, comm, res)
                    slab_loc = gather(slab_cache, slab, res)
                    if fused:
                        # one dispatch: lazy wake + same-community min
                        # (first iteration: everyone awake => chg all-ones)
                        chg_loc = (exchange.gather(changed_prev, loc)
                                   if changed_prev is not None else ones_loc)
                        new = be.partition_split_fused(
                            sweeps, res.inputs, comm_loc, slab_loc,
                            chg_loc, bound)[: part.size]
                    else:
                        if prune and changed_prev is not None:
                            sactive[rng] = be.partition_split_wake(
                                sweeps, res.inputs, comm_loc,
                                exchange.gather(changed_prev,
                                                loc))[: part.size]
                        new = be.partition_split(
                            sweeps, res.inputs, comm_loc, slab_loc,
                            sactive[rng], bound)[: part.size]
                    exchange.scatter(slab_next, rng, new)
                if cfg.shortcut:
                    # global pointer jump — O(n) vertex pass, same position
                    # as the in-core sweep body's `min(new, new[new])`
                    slab_next = np.minimum(slab_next, slab_next[slab_next])
                changed = slab_next != slab
                delta = int(changed.sum())
                if do_profile and cfg.profile == "full":
                    split_rows.append((split_iterations, active_proxy,
                                       delta))
                changed_prev = changed
                slab = slab_next
                if slab_cache is not None:
                    slab_cache.advance(changed)
                split_iterations += 1
        sp_split.set(iterations=split_iterations)
        labels = slab
    t_split = time.perf_counter() - t0

    peak = ledger.peak
    loads = loader.loads
    # Cached gathers bypass the Exchange accounting; fold the bytes the
    # caches did move (builds + changed-entry refreshes) back in so
    # exchange_bytes stays "label traffic a wire layout would carry".
    exchange_bytes = exchange.bytes + sum(c.bytes for c in caches)
    saved = sum(c.bytes_saved for c in caches)
    hits = sum(c.hits for c in caches)
    for c in caches:
        c.drop()
    loader.clear()
    profile = None
    if do_profile:
        profile = ConvergenceProfile(
            propagation=phase_from_rows("propagation", prop_rows),
            split=(phase_from_rows("split", split_rows)
                   if split_rows else None),
            n=n)
    _M_FITS.inc()
    _M_EXCHANGE.inc(exchange_bytes)
    return OocRun(
        labels=labels, backend=name, lpa_iterations=lpa_iterations,
        split_iterations=split_iterations, lpa_seconds=t_lpa,
        split_seconds=t_split, plan_seconds=t_plan,
        num_partitions=plan.num_partitions, peak_resident_bytes=peak,
        budget=budget, halo_vertices=plan.halo_vertices,
        exchange_bytes=exchange_bytes, partition_loads=loads,
        cache_hit=cache_hit, plan_stats=plan.stats(),
        fused=fused, prefetches=loader.prefetches,
        prefetch_hits=loader.prefetch_hits,
        halo_cache_bytes_saved=saved, halo_cache_hits=hits,
        profile=profile,
    )


class _Prepare:
    """Adapter handing the loader the backend's device-side prep."""

    def __init__(self, backend, shapes: PartitionShapes,
                 config: EngineConfig):
        self.backend, self.shapes, self.config = backend, shapes, config

    def estimate(self, part) -> int:
        return self.backend.partition_prepare_nbytes(self.shapes)

    def build(self, resident):
        return self.backend.prepare_partition(resident, self.shapes,
                                              self.config)


class Exchange:
    """Per-sweep halo-label gather/scatter, with byte accounting.

    ``gather`` pulls a partition's local view (owned rows followed by
    halo imports) out of a shared global array, padded to the run's
    uniform local length; ``scatter`` writes the owned rows back.  The
    accumulated byte count is the label traffic a multi-process layout
    would put on the wire — reported in ``OocRun.exchange_bytes``.
    """

    def __init__(self, shapes: PartitionShapes):
        self.shapes = shapes
        self.bytes = 0

    def gather(self, global_arr: np.ndarray, local_ids: np.ndarray,
               ) -> np.ndarray:
        out = np.zeros(self.shapes.n_loc, dtype=global_arr.dtype)
        out[: len(local_ids)] = global_arr[local_ids]
        self.bytes += int(len(local_ids)) * global_arr.itemsize
        return out

    def scatter(self, global_arr: np.ndarray, rng: slice,
                values: np.ndarray) -> None:
        global_arr[rng] = values
        self.bytes += values.nbytes
