"""The unified Engine: one entry point for every GSL-LPA execution path.

    from repro.engine import Engine, EngineConfig

    eng = Engine(EngineConfig(backend="auto"))
    result = eng.fit(graph)                 # DetectionResult
    result = eng.fit(graph2)                # same bucket -> no recompile
    result = eng.fit(graph2, init_labels=result.labels)   # warm start
    results = eng.fit_many([g1, g2, g3])    # one batched dispatch
    results = eng.fit_many(posts, init_labels=prev_labels,
                           init_active=frontiers)   # batched warm re-detect

``fit`` is backend-agnostic: it buckets the graph, fetches (or builds) the
compiled plan from the shape-bucketed cache, runs the backend, applies the
host split when requested, compacts labels, and optionally attaches
quality metrics — returning the same :class:`DetectionResult` regardless
of execution strategy.

Warm starts: ``init_labels`` seeds propagation with an existing
assignment; ``init_active`` seeds the unprocessed flags (GVE-LPA pruning
rule — pass a delta's affected frontier so only changed neighborhoods
get re-processed).  With ``warm_start="auto"`` the engine keeps a
bounded LRU cache of ``graph_fingerprint -> last labels`` updated on
every fit (solo or batched member), so re-fitting a structurally
identical graph warm-starts automatically.  Batched warm re-detection is
bit-identical to solo warm ``fit`` on each member (pinned in
tests/test_stream.py).
"""
from __future__ import annotations

import threading
from collections import OrderedDict

import jax.numpy as jnp
import numpy as np

import repro.engine.backends  # noqa: F401  (registers built-in strategies)
from repro.core.batch import GraphBatch
from repro.core.graph import Graph, graph_fingerprint
from repro.core.split import split_bfs_host
from repro.engine.bucketing import batch_bucket_for, bucket_for
from repro.engine.cache import GLOBAL_CACHE, CompileCache, trace_context
from repro.engine.config import DetectionResult, EngineConfig
from repro.engine.registry import (
    choose_backend,
    choose_backend_batch,
    get_backend,
)
from repro.obs import REGISTRY, span


def _as_graph(graph) -> Graph:
    """Accept a Graph or a path to a graph file (mtx / SNAP edge list).

    Paths go through :func:`repro.io.load_graph` — first fit of a file
    parses + caches the CSR on disk, later fits (any process) mmap it
    back.  Imported lazily: the io layer is optional on the hot path.
    """
    if isinstance(graph, Graph):
        return graph
    if isinstance(graph, str) or hasattr(graph, "__fspath__"):
        from repro.io import load_graph
        return load_graph(graph)
    raise TypeError(f"fit expects a Graph or a graph-file path, got "
                    f"{type(graph).__name__}")


def _compact_host(labels: np.ndarray) -> tuple[np.ndarray, int]:
    """Dense [0, K) relabeling, host-side (same rank order as
    ``split.compact_labels``, but shape-polymorphic for free)."""
    uniq, inv = np.unique(np.asarray(labels), return_inverse=True)
    return inv.astype(np.int32), len(uniq)


def _check_init_labels(labels, n: int, name: str) -> np.ndarray:
    """Validate warm-start labels: (n,) vertex-id-valued.  The usual way
    to trip this is feeding *stale* labels from a pre-delta graph whose
    vertex count has since changed — reject loudly, never truncate."""
    labels = np.asarray(labels)
    if labels.shape != (n,):
        raise ValueError(
            f"{name} has shape {labels.shape} for a graph with {n} "
            f"vertices — stale warm-start labels from a different graph? "
            f"Re-detect cold or extend the labels to the new vertex set.")
    labels = labels.astype(np.int32)
    if n and (labels.min() < 0 or labels.max() >= n):
        raise ValueError(f"{name} must be vertex-id-valued in [0, {n})")
    return labels


def _check_init_active(active, n: int, name: str) -> np.ndarray:
    active = np.asarray(active).astype(bool)
    if active.shape != (n,):
        raise ValueError(f"{name} has shape {active.shape} for a graph "
                         f"with {n} vertices")
    return active


class _WarmCache:
    """Bounded LRU of ``graph_fingerprint -> last compacted labels``.

    Per-session state for ``warm_start="auto"``: every fit stores its
    result labels under the graph's structural fingerprint, and a later
    fit of a structurally identical graph starts from them.  The bound
    keeps a long streaming session from accumulating one labels array
    per graph ever served (tests pin the no-unbounded-growth property).

    Thread-safe: one Engine is shared by every session of the serving
    tier, so ``get``/``put`` race from the micro-batcher worker, client
    threads calling ``fit`` directly, and ``stats()`` pollers.  An
    ``OrderedDict`` mutated by ``move_to_end``/``popitem`` corrupts
    under that interleaving (the compile caches in ``engine/cache.py``
    always took a lock; this cache historically did not), so every
    access holds the lock.
    """

    def __init__(self, max_entries: int, scope=None):
        self.max_entries = int(max_entries)
        self._entries: OrderedDict[tuple, np.ndarray] = OrderedDict()
        self._lock = threading.Lock()
        # Registry handles (metrics write-through; ``stats()`` views stay
        # computed from the authoritative OrderedDict, not read back).
        self._m_hits = scope.counter("warm_hits") if scope else None
        self._m_misses = scope.counter("warm_misses") if scope else None
        self._m_evict = scope.counter("warm_evictions") if scope else None
        self._m_entries = scope.gauge("warm_entries") if scope else None

    def get(self, fp: tuple) -> np.ndarray | None:
        with self._lock:
            labels = self._entries.get(fp)
            if labels is not None:
                self._entries.move_to_end(fp)
        if self._m_hits is not None:
            (self._m_hits if labels is not None else self._m_misses).inc()
        return labels

    def put(self, fp: tuple, labels: np.ndarray) -> None:
        evicted = 0
        with self._lock:
            self._entries[fp] = labels
            self._entries.move_to_end(fp)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                evicted += 1
            count = len(self._entries)
        if self._m_entries is not None:
            self._m_entries.set(count)
            if evicted:
                self._m_evict.inc(evicted)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


class Engine:
    """Pluggable-backend GSL-LPA engine with a shape-bucketed jit cache.

    ``cache=None`` shares the process-wide :data:`GLOBAL_CACHE`, so
    independent Engine instances (and the legacy ``gsl_lpa`` wrapper)
    reuse each other's compiled plans.  The warm-start cache, by
    contrast, is per-engine session state.
    """

    def __init__(self, config: EngineConfig | None = None,
                 cache: CompileCache | None = None):
        self.config = config if config is not None else EngineConfig()
        self.cache = cache if cache is not None else GLOBAL_CACHE
        self._obs = REGISTRY.scope("engine")
        self._warm = _WarmCache(self.config.warm_cache_size,
                                scope=self._obs)
        self._m_fits = self._obs.counter("fits")
        self._m_batch_fits = self._obs.counter("batch_fits")
        # Quality telemetry scope ("engine.quality.*") — claimed eagerly
        # so concurrent fits never race a lazy scope() call.
        self._q_obs = self._obs.scope("quality") \
            if self.config.quality != "off" else None

    # --- warm-start resolution ---

    def _auto_fp(self, graph: Graph) -> tuple | None:
        return graph_fingerprint(graph) \
            if self.config.warm_start == "auto" else None

    def _resolve_warm(self, n: int, init_labels, init_active,
                      fp: tuple | None, name: str):
        """Explicit init labels win; else consult the warm cache.

        A frontier seed only means anything *relative to* a previous
        assignment — restricting a cold singleton start to the frontier
        would freeze every other vertex at its own label and return
        garbage.  So when no warm labels resolve (explicit None plus a
        cache miss, e.g. after LRU eviction), ``init_active`` is dropped
        and the fit degrades to a full cold detection.
        """
        warm_started = init_labels is not None
        if init_labels is None and fp is not None:
            init_labels = self._warm.get(fp)
            warm_started = init_labels is not None
        if init_active is not None:  # validate even when about to drop it
            init_active = _check_init_active(init_active, n,
                                             name.replace("labels", "active"))
        if init_labels is not None:
            init_labels = _check_init_labels(init_labels, n, name)
        else:
            init_active = None
        return init_labels, init_active, warm_started

    # --- solo fit ---

    def fit(self, graph, init_labels=None, init_active=None, *,
            backend: str | None = None,
            memory_budget: int | str | None = None) -> DetectionResult:
        """Detect communities; returns a unified :class:`DetectionResult`.

        ``graph`` may be a :class:`Graph` or a path to a graph file
        (``.mtx`` / SNAP edge list): paths route through
        :func:`repro.io.load_graph`, so the parse is paid once per file
        content and later fits mmap the cached CSR.

        ``memory_budget`` (bytes, or ``"64MB"``-style; defaults to
        ``config.memory_budget``) auto-routes the fit: in-core when the
        graph's edge arrays fit the budget, otherwise out-of-core —
        partitioned CSR slices swept one-resident-at-a-time with
        halo-label exchange (:mod:`repro.partition`), labels
        bit-identical to the in-core path.  For paths the routing
        decision reads only the store entry's metadata, so a
        bigger-than-budget file is never materialized.

        ``init_labels``: optional (n,) vertex-id-valued initial assignment
        (warm start / incremental re-detection).  ``init_active``:
        optional (n,) unprocessed-seed mask — pass the delta's affected
        frontier (``repro.core.delta.affected_frontier``) so propagation
        is restricted to changed neighborhoods; honored only alongside
        warm labels (see ``_resolve_warm``).  ``backend`` overrides the
        configured strategy for this call only.
        """
        budget = memory_budget if memory_budget is not None \
            else self.config.memory_budget
        if budget is not None:
            from repro.partition.ooc import (
                IN_CORE_EDGE_BYTES,
                in_core_edge_bytes,
                open_source,
            )
            from repro.partition.plan import parse_bytes
            budget = parse_bytes(budget)
            if isinstance(graph, Graph):
                # metadata-only routing check; build no source unless
                # the partitioned path is actually taken
                too_big = graph.m_pad * IN_CORE_EDGE_BYTES > budget
                source = open_source(graph) if too_big else None
            else:
                source = open_source(graph)  # store-metadata handle
                too_big = in_core_edge_bytes(source) > budget
            if too_big:
                return self._fit_ooc(source, budget, init_labels,
                                     init_active, backend)
            if source is not None:
                # fits in core: materialize from the handle we already
                # opened — no second content hash / store open
                graph = source.to_graph()
        graph = _as_graph(graph)
        fp = self._auto_fp(graph)
        init_labels, init_active, warm_started = self._resolve_warm(
            graph.n, init_labels, init_active, fp, "init_labels")
        result = self._fit_resolved(graph, init_labels, init_active,
                                    backend, warm_started)
        if fp is not None:
            self._warm.put(fp, result.labels)
        return result

    def _fit_ooc(self, source, budget: int, init_labels, init_active,
                 backend: str | None) -> DetectionResult:
        """Out-of-core partitioned fit over an array source."""
        from repro.partition.ooc import fit_out_of_core
        cfg = self.config
        if cfg.compute_metrics:
            raise ValueError(
                "compute_metrics needs the full graph on device; compute "
                "quality metrics separately after an out-of-core fit")
        fp = tuple(source.fingerprint()) \
            if cfg.warm_start == "auto" and source.fingerprint() else None
        init_labels, init_active, warm_started = self._resolve_warm(
            source.n, init_labels, init_active, fp, "init_labels")

        with span("engine.fit_ooc", n=source.n):
            run = fit_out_of_core(source, cfg, memory_budget=budget,
                                  backend=backend, cache=self.cache,
                                  init_labels=init_labels,
                                  init_active=init_active)
            with span("engine.compact") as compact:
                labels, k = _compact_host(run.labels)

        self._m_fits.inc()
        result = DetectionResult(
            labels=labels, num_communities=k, backend=run.backend,
            lpa_iterations=run.lpa_iterations,
            split_iterations=run.split_iterations,
            edge_slots=0,  # each partition sweeps a window of its own
            timings={"prepare": run.plan_seconds,
                     "propagation": run.lpa_seconds,
                     "split": run.split_seconds, "compact": compact.dur},
            bucket=(source.n, source.num_edges), cache_hit=run.cache_hit,
            warm_started=warm_started,
            partitions=run.num_partitions, ooc=run.stats(),
            profile=getattr(run, "profile", None),
        )
        if cfg.quality != "off":
            # Host-only report: the full graph never sits on the device
            # out-of-core, so modularity and the disconnected fraction
            # stay None here; sizes / count / churn still flow.
            self._attach_quality(result, None, init_labels)
        if fp is not None:
            self._warm.put(fp, result.labels)
        return result

    def _fit_resolved(self, graph: Graph, init_labels, init_active,
                      backend: str | None, warm_started: bool,
                      ) -> DetectionResult:
        """One detection with warm state already resolved + validated
        (no auto-cache lookups or updates — callers own those)."""
        cfg = self.config
        name = backend or cfg.backend
        if name == "auto":
            name = choose_backend(graph, cfg)
        be = get_backend(name)

        bucket = bucket_for(graph, bucketing=cfg.bucketing,
                            min_vertex_bucket=cfg.min_vertex_bucket,
                            min_edge_bucket=cfg.min_edge_bucket)
        key = (name, bucket, cfg.bucketing, cfg.algo_key(), be.plan_key(cfg))
        with span("engine.fit", backend=name, n=graph.n):
            plan, cache_hit = self.cache.get_or_build(
                key, lambda: be.build(bucket, cfg))

            with span("engine.prepare") as prepare:
                inputs = be.prepare(graph, bucket, cfg)

            # the backend times its phases as engine.propagate and
            # engine.split inside this span
            with trace_context(name, bucket), span("engine.dispatch"):
                run = be.run(plan, inputs, graph.n, init_labels,
                             init_active)
            labels = np.asarray(run.labels)[: graph.n]

            split_seconds = run.split_seconds
            if cfg.split == "bfs_host":
                with span("engine.split_host") as split_host:
                    labels = split_bfs_host(graph, labels)
                split_seconds += split_host.dur

            with span("engine.compact") as compact:
                labels, k = _compact_host(labels)

        self._m_fits.inc()
        result = DetectionResult(
            labels=labels, num_communities=k, backend=name,
            lpa_iterations=run.lpa_iterations,
            split_iterations=run.split_iterations,
            edge_slots=run.edge_slots,
            timings={"prepare": prepare.dur, "propagation": run.lpa_seconds,
                     "split": split_seconds, "compact": compact.dur},
            bucket=tuple(bucket), cache_hit=cache_hit,
            warm_started=warm_started,
            profile=run.profile,
        )
        if cfg.compute_metrics:
            self._attach_metrics(result, graph)
        if cfg.quality != "off":
            self._attach_quality(result, graph, init_labels)
        return result

    def _attach_metrics(self, result: DetectionResult, graph: Graph) -> None:
        from repro.core.modularity import modularity
        result.modularity = float(
            modularity(graph, jnp.asarray(result.labels)))
        result.check_connected(graph)

    def _attach_quality(self, result: DetectionResult, graph,
                        prev_labels) -> None:
        """Post-fit quality telemetry (``EngineConfig.quality != "off"``).

        Runs strictly *after* convergence, on the final labels at a host
        stage boundary — it can never perturb the sweep loop, which is
        why ``quality`` stays out of ``algo_key()`` and labels/iteration
        counts are bit-identical across modes.  ``prev_labels`` is the
        resolved warm-start assignment (the previous fit of this
        fingerprint/tenant in steady state) — the churn baseline.
        ``graph=None`` produces the host-only report of the out-of-core
        path.

        Cost tiering: "basic" is host-only (bincount sizes + churn —
        negligible next to a fit, the <=5% CI gate measures it); only
        "full" pays the per-fit device passes (modularity ~ one extra
        sweep, connectivity via the fingerprint-cached
        ``check_connected``).
        """
        cfg = self.config
        from repro.obs.quality import compute_quality, record_report
        with span("engine.quality", mode=cfg.quality):
            full = cfg.quality == "full"
            if full and graph is not None:
                result.check_connected(graph)  # fingerprint-cached pass
            result.quality = compute_quality(
                result.labels, mode=cfg.quality,
                graph=graph if full else None,
                prev_labels=prev_labels,
                num_communities=result.num_communities,
                modularity=result.modularity,
                disconnected_fraction=result.disconnected_fraction)
            if result.modularity is None:
                result.modularity = result.quality.modularity
            record_report(self._q_obs, result.quality)

    # --- batched fit ---

    def fit_many(self, graphs, *, init_labels=None, init_active=None,
                 backend: str | None = None) -> list[DetectionResult]:
        """Detect communities for k graphs in one batched device dispatch.

        The graphs are packed into a disjoint-union super-graph
        (:class:`repro.core.batch.GraphBatch`) and executed by the
        backend's batched plan, cached per *batch bucket* — a
        (graph-count, total-vertex, total-edge, max-degree) shape key —
        so mixed traffic reuses compiled plans.  Per-graph results are
        bit-identical to ``fit`` on each graph alone, cold or warm (the
        parity suites in tests/test_batch.py and tests/test_stream.py
        pin this for ``segment`` and ``tile`` across every split mode).
        Backends without ``supports_batch`` (the ``sharded`` strategy)
        fall back to sequential ``fit`` calls with identical warm-start
        semantics.

        ``init_labels`` / ``init_active``: optional length-k sequences of
        per-member warm-start labels and unprocessed-seed masks (None
        entries for cold members) — the streaming re-detection path:
        apply each member's delta, then pass the previous labels and the
        delta's affected frontier.  With ``warm_start="auto"``, members
        without explicit labels consult the warm cache; lookups snapshot
        the cache *before* the dispatch, so members never warm-start off
        each other within one batch, and every member's result is stored
        back afterwards.

        Batch-level timings (prepare/propagation/split) are attributed
        pro rata by each graph's share of packed work (vertices + edges);
        compaction and the host BFS split are timed per graph.
        """
        graphs = [_as_graph(g) for g in graphs]
        if not graphs:
            return []
        cfg = self.config
        k = len(graphs)
        init_labels = self._per_member(init_labels, k, "init_labels")
        init_active = self._per_member(init_active, k, "init_active")

        fps = [self._auto_fp(g) for g in graphs]
        resolved = [
            self._resolve_warm(g.n, init_labels[i], init_active[i], fps[i],
                               f"init_labels[{i}]")
            for i, g in enumerate(graphs)
        ]
        labels_r = [r[0] for r in resolved]
        active_r = [r[1] for r in resolved]
        warm_r = [r[2] for r in resolved]

        name = backend or cfg.backend
        if name == "auto":
            name = choose_backend_batch(graphs, cfg)
        be = get_backend(name)
        if not getattr(be, "supports_batch", False):
            # Sequential fallback keeps batched semantics: warm state was
            # resolved against the pre-dispatch cache snapshot above, so
            # members never warm off each other mid-batch.
            results = [self._fit_resolved(g, labels_r[i], active_r[i],
                                          name, warm_r[i])
                       for i, g in enumerate(graphs)]
        else:
            results = self._fit_many_packed(graphs, labels_r, active_r,
                                            warm_r, name, be)
        for fp, res in zip(fps, results):
            if fp is not None:
                self._warm.put(fp, res.labels)
        return results

    @staticmethod
    def _per_member(seq, k: int, name: str) -> list:
        if seq is None:
            return [None] * k
        seq = list(seq)
        if len(seq) != k:
            raise ValueError(f"{name} has {len(seq)} entries for a batch "
                             f"of {k} graphs")
        return seq

    def _fit_many_packed(self, graphs, labels_r, active_r, warm_r,
                         name: str, be) -> list[DetectionResult]:
        cfg = self.config
        with span("engine.fit_many", backend=name, k=len(graphs)):
            with span("engine.prepare") as prepare:
                batch = GraphBatch.pack(graphs)
                bucket = batch_bucket_for(
                    batch, bucketing=cfg.bucketing,
                    min_vertex_bucket=cfg.min_vertex_bucket,
                    min_edge_bucket=cfg.min_edge_bucket)
                key = (name, "batch", bucket, cfg.bucketing, cfg.algo_key(),
                       be.plan_key(cfg))
                plan, cache_hit = self.cache.get_or_build(
                    key, lambda: be.build_batch(bucket, cfg))
                inputs = be.prepare_batch(batch, bucket, cfg)
                # Per-member labels are local-coordinate by construction
                # (a solo graph's vertex ids are its local ids), so
                # packing is a plain offset-sliced concatenation.
                labels0 = batch.pack_labels(labels_r)
                active0 = batch.pack_active(active_r)

            with trace_context(name, ("batch", *bucket)), \
                    span("engine.dispatch"):
                run = be.run_batch(plan, inputs, labels0, active0)
            labels_all = np.asarray(run.labels)

            # The one device dispatch serves every member, so per-member
            # stage seconds are not measurable; the real batch-level stage
            # timings live on the spans above, and each member carries an
            # explicitly-labeled work-share estimate ("prorated_*" —
            # vertices + edges pro rata), never dressed up as a
            # measurement.  Host split/compact run per member and stay
            # real timings.
            work = np.asarray(batch.sizes + batch.edge_counts,
                              dtype=np.float64)
            weights = work / work.sum() if work.sum() > 0 \
                else np.full(len(graphs), 1.0 / len(graphs))

            # Per member after the one dispatch: unpack its rows, compact
            # its labels and build its result.
            with span("engine.members", k=len(graphs)):
                results = []
                for i, graph in enumerate(graphs):
                    lo, hi = int(batch.offsets[i]), int(batch.offsets[i + 1])
                    labels = labels_all[lo:hi]
                    w = float(weights[i])

                    split_host = 0.0
                    if cfg.split == "bfs_host":
                        with span("engine.split_host") as host_split:
                            labels = split_bfs_host(graph, labels)
                        split_host = host_split.dur

                    with span("engine.compact") as compact:
                        labels, k = _compact_host(labels)

                    result = DetectionResult(
                        labels=labels, num_communities=k, backend=name,
                        lpa_iterations=int(run.lpa_iterations[i]),
                        split_iterations=int(run.split_iterations[i]),
                        edge_slots=run.edge_slots,
                        timings={"prorated_prepare": prepare.dur * w,
                                 "prorated_propagation": run.lpa_seconds * w,
                                 "prorated_split": run.split_seconds * w,
                                 "split": split_host, "compact": compact.dur},
                        bucket=tuple(bucket), cache_hit=cache_hit,
                        warm_started=warm_r[i],
                        batch_size=len(graphs), batch_index=i,
                        profile=run.profile[i] if run.profile else None,
                    )
                    if cfg.compute_metrics:
                        self._attach_metrics(result, graph)
                    if cfg.quality != "off":
                        self._attach_quality(result, graph, labels_r[i])
                    results.append(result)
        self._m_batch_fits.inc()
        self._m_fits.inc(len(graphs))
        return results

    def stats(self) -> dict:
        """Cache + trace observability (for serving dashboards / tests)."""
        from repro.engine.cache import TRACE_LOG
        return {**self.cache.stats(), "traces": TRACE_LOG.snapshot(),
                "warm_entries": len(self._warm),
                "warm_capacity": self._warm.max_entries}
