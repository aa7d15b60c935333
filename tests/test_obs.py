"""Unified telemetry layer: registry, span tracer, convergence profiles.

The load-bearing contract is **bit parity**: ``EngineConfig.profile``
("off" | "convergence" | "full") must never change a single label or
iteration count — solo, batched, or out-of-core, on every backend and
split mode.  The profile buffer rides the while_loop state and never
feeds back, so parity holds by construction; these tests pin it.

Also pinned: the figure-1 profile values themselves (the frontier-decay
curve the FLPA comparison reads), Chrome-trace export well-formedness,
registry thread-safety, and key-parity of the legacy ``stats()`` dicts
that now read through the registry.
"""
import json
import threading
import time

import numpy as np
import pytest

from repro.engine import CompileCache, Engine, EngineConfig
from repro.graphgen import erdos_renyi, karate_club
from repro.graphgen.synthetic import figure1_graph
from repro.obs import (
    REGISTRY,
    TRACER,
    ConvergenceProfile,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Tracer,
    span,
)
from repro.obs.convergence import phase_from_rows

BACKENDS = ("segment", "tile")
SPLITS = ("none", "lp", "lpp")


def fresh_engine(**kw):
    return Engine(EngineConfig(**kw), cache=CompileCache())


# --- metrics registry ---

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("g")
    g.set(7)
    g.add(-2)
    assert g.value == 5
    h = reg.histogram("h", (1, 10, 100))
    for v in (0.5, 5, 50, 500):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["buckets"]["overflow"] == 1
    assert s["mean"] == pytest.approx((0.5 + 5 + 50 + 500) / 4)
    assert h.quantile(0.5) in (5.0, 50.0)


def test_registry_snapshot_and_render():
    reg = MetricsRegistry()
    reg.counter("a.n").inc(3)
    reg.histogram("a.h", (1,)).observe(2)
    snap = reg.snapshot()
    assert snap["a.n"] == 3
    assert snap["a.h"]["count"] == 1
    text = reg.render_text()
    assert "a.n  3" in text and "a.h" in text


def test_scope_dedupe_and_release():
    reg = MetricsRegistry()
    s1, s2 = reg.scope("svc"), reg.scope("svc")
    assert s1.label == "svc" and s2.label == "svc#1"
    s1.counter("x").inc()
    s2.counter("x").inc(2)
    child = s1.scope("inner")
    child.counter("y").inc()
    snap = reg.snapshot()
    assert snap["svc.x"] == 1 and snap["svc#1.x"] == 2
    assert snap["svc.inner.y"] == 1
    s1.release()               # drops svc.* including children, frees label
    snap = reg.snapshot()
    assert "svc.x" not in snap and "svc.inner.y" not in snap
    assert snap["svc#1.x"] == 2
    s3 = reg.scope("svc")      # label is reusable after release
    assert s3.label == "svc"
    # double release is harmless
    s1.release()


def test_registry_type_conflict():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_registry_threaded_stress():
    reg = MetricsRegistry()
    c = reg.counter("hot")
    h = reg.histogram("lat", (1, 10))
    scopes = []

    def work(i):
        for _ in range(500):
            c.inc()
            h.observe(i)
        s = reg.scope("worker")
        s.counter("n").inc()
        scopes.append(s)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8 * 500
    assert h.count == 8 * 500
    # every thread got a distinct scope label
    assert len({s.label for s in scopes}) == 8
    for s in scopes:
        s.release()


# --- span tracer / chrome export ---

def test_spans_nest_and_export_chrome(tmp_path):
    tr = Tracer()
    with tr.span("outer", k=1) as outer:
        with tr.span("inner") as inner:
            assert tr.current() is inner
        assert tr.current() is outer
        outer.set(result="done")
    assert tr.current() is None
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]
    by_name = {s.name: s for s in spans}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["outer"].attrs == {"k": 1, "result": "done"}
    assert by_name["outer"].dur >= by_name["inner"].dur >= 0

    out = tmp_path / "trace.json"
    n = tr.export_chrome(out)
    events = json.loads(out.read_text())
    assert n == len(events) == 2
    for ev in events:
        assert set(ev) == {"name", "ph", "pid", "tid", "ts", "dur", "args"}
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0 and ev["dur"] >= 0
    inner_ev = next(e for e in events if e["name"] == "inner")
    assert inner_ev["args"]["parent_span"] == by_name["outer"].span_id


def test_tracer_disabled_is_free():
    tr = Tracer(enabled=False)
    with tr.span("x") as s:
        s.set(ignored=True)
    assert tr.spans() == []


def test_disabled_span_still_times_the_block():
    tr = Tracer(enabled=False)
    with tr.span("x") as s:
        time.sleep(0.01)
    assert s.dur >= 0.01
    assert tr.spans() == [] and tr.current() is None


def test_engine_fit_emits_spans():
    g = karate_club()[0]
    TRACER.reset()
    fresh_engine().fit(g)
    names = {s.name for s in TRACER.spans("engine.")}
    assert {"engine.fit", "engine.prepare", "engine.dispatch"} <= names


STAGES = ("engine.prepare", "engine.propagate", "engine.split",
          "engine.compact")


def _profiled_fit(tmp_path, backend):
    import glob

    import jax
    from jax.profiler import ProfileData
    g = erdos_renyi(300, 5.0, seed=5)
    eng = fresh_engine(backend=backend)
    eng.fit(g)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.fit(g)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("engine.")]


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_spans_in_profiler_trace(tmp_path, backend):
    """Each engine stage is a host annotation of the profiler's trace,
    nested inside engine.fit, on the clock of the device operations."""
    events = _profiled_fit(tmp_path, backend)
    fits = [e for e in events if e[0] == "engine.fit"]
    assert len(fits) == 1
    _, f0, f1 = fits[0]
    for stage in STAGES:
        inside = [e for e in events if e[0] == stage]
        assert len(inside) == 1, stage
        assert f0 <= inside[0][1] <= inside[0][2] <= f1, stage
    starts = [next(e[1] for e in events if e[0] == s) for s in STAGES]
    assert starts == sorted(starts)


def _record_spans(monkeypatch, modules):
    """Wrap each module's ``span`` to keep the spans it yields."""
    import contextlib
    seen = []
    for mod in modules:
        real = mod.span

        @contextlib.contextmanager
        def keep(name, real=real, **attrs):
            with real(name, **attrs) as s:
                yield s
            seen.append(s)
        monkeypatch.setattr(mod, "span", keep)
    return seen


@pytest.mark.parametrize("enabled", [True, False], ids=["on", "off"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_timings_are_span_durations(monkeypatch, backend, enabled):
    import repro.engine.backends.segment as seg_mod
    import repro.engine.backends.tile as tile_mod
    import repro.engine.engine as engine_mod
    seen = _record_spans(monkeypatch, (engine_mod, seg_mod, tile_mod))
    monkeypatch.setattr(TRACER, "enabled", enabled)
    TRACER.reset()
    r = fresh_engine(backend=backend).fit(erdos_renyi(200, 5.0, seed=2))
    dur = {s.name: s.dur for s in seen}
    assert r.timings == {"prepare": dur["engine.prepare"],
                         "propagation": dur["engine.propagate"],
                         "split": dur["engine.split"],
                         "compact": dur["engine.compact"]}
    assert all(v > 0 for v in r.timings.values())
    recorded = {s.name for s in TRACER.spans("engine.")}
    assert recorded == (set(dur) if enabled else set())


# --- convergence profiles: bit parity ---

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("split", SPLITS)
def test_profile_solo_bit_parity(backend, split):
    g = erdos_renyi(120, 5.0, seed=7)
    base = fresh_engine(backend=backend, split=split).fit(g)
    assert base.profile is None
    for mode in ("convergence", "full"):
        r = fresh_engine(backend=backend, split=split, profile=mode).fit(g)
        assert np.array_equal(r.labels, base.labels)
        assert r.lpa_iterations == base.lpa_iterations
        assert r.split_iterations == base.split_iterations
        assert isinstance(r.profile, ConvergenceProfile)
        prop = r.profile.propagation
        assert prop.num_sub_sweeps == 2 * r.lpa_iterations
        assert (prop.active >= 0).all() and (prop.changed >= 0).all()
        assert (prop.active <= g.n).all()
        # a vertex only changes label as a candidate
        assert (prop.changed <= prop.active).all()
        if mode == "full" and split in ("lp", "lpp"):
            assert r.profile.split is not None
        else:
            assert r.profile.split is None


@pytest.mark.parametrize("backend", BACKENDS)
def test_profile_batched_bit_parity(backend):
    graphs = [erdos_renyi(100, 4.0, seed=1), karate_club()[0],
              erdos_renyi(100, 4.0, seed=2)]
    base = fresh_engine(backend=backend, split="lp").fit_many(graphs)
    eng = fresh_engine(backend=backend, split="lp", profile="full")
    prof = eng.fit_many(graphs)
    solo = [fresh_engine(backend=backend, split="lp", profile="full").fit(g)
            for g in graphs]
    for b, p, s, g in zip(base, prof, solo, graphs):
        assert np.array_equal(p.labels, b.labels)
        assert p.lpa_iterations == b.lpa_iterations
        assert isinstance(p.profile, ConvergenceProfile)
        assert p.profile.n == g.n
        # the batched member's curve is the solo curve (per-slot
        # segment-sums see only that member's vertices)
        assert np.array_equal(p.profile.propagation.active[:2 * p.lpa_iterations],
                              s.profile.propagation.active[:2 * p.lpa_iterations])
        assert np.array_equal(p.profile.propagation.changed[:2 * p.lpa_iterations],
                              s.profile.propagation.changed[:2 * p.lpa_iterations])


@pytest.mark.parametrize("fuse", ("auto", "off"))
def test_profile_ooc_bit_parity(fuse):
    from repro.partition.ooc import fit_out_of_core, open_source
    g = erdos_renyi(200, 6.0, seed=11)
    src = open_source(g)
    runs = {}
    for mode in ("off", "convergence", "full"):
        cfg = EngineConfig(split="lp", profile=mode, fuse_sweeps=fuse)
        runs[mode] = fit_out_of_core(src, cfg, memory_budget="1MB",
                                     num_partitions=3)
    base = runs["off"]
    assert base.profile is None
    for mode in ("convergence", "full"):
        r = runs[mode]
        assert np.array_equal(r.labels, base.labels)
        assert r.lpa_iterations == base.lpa_iterations
        assert r.split_iterations == base.split_iterations
        assert r.profile.propagation.num_sub_sweeps == 2 * r.lpa_iterations
    assert runs["convergence"].profile.split is None
    assert runs["full"].profile.split is not None
    # ooc propagation curve == in-core curve (exact, not a proxy)
    incore = fresh_engine(split="lp", profile="full").fit(g)
    assert np.array_equal(runs["full"].profile.propagation.active,
                          incore.profile.propagation.active)
    assert np.array_equal(runs["full"].profile.propagation.changed,
                          incore.profile.propagation.changed)


# --- convergence profiles: figure-1 correctness ---

@pytest.mark.parametrize("backend", BACKENDS)
def test_profile_figure1_values(backend):
    g, _, _ = figure1_graph()
    r = fresh_engine(backend=backend, split="lp", profile="full").fit(g)
    p = r.profile
    assert p.n == g.n == 10
    assert p.propagation.sweep.tolist() == [0, 1, 2, 3, 4, 5]
    assert p.propagation.active.tolist() == [6, 4, 6, 3, 3, 0]
    assert p.propagation.changed.tolist() == [6, 3, 2, 0, 0, 0]
    assert not p.propagation.truncated
    decay = p.frontier_decay()
    assert decay.tolist() == pytest.approx([0.6, 0.4, 0.6, 0.3, 0.3, 0.0])
    # split phase: 2 min-label sweeps separate the bridged lobes
    assert p.split is not None
    assert p.split.num_sub_sweeps == r.split_iterations == 2
    assert p.split.changed.tolist()[-1] == 0     # fixed point reached
    assert not p.split.truncated
    d = p.to_dict()
    assert d["propagation"]["active"] == [6, 4, 6, 3, 3, 0]
    json.dumps(d)                                 # JSON-serializable


def test_phase_from_rows_roundtrip():
    rows = [(0, 10, 4), (1, 6, 1), (2, 2, 0)]
    ph = phase_from_rows("propagation", rows)
    assert ph.sweep.tolist() == [0, 1, 2]
    assert ph.active.tolist() == [10, 6, 2]
    assert ph.changed.tolist() == [4, 1, 0]
    assert phase_from_rows("split", []).num_sub_sweeps == 0


def test_profile_off_attaches_nothing():
    g = karate_club()[0]
    r = fresh_engine().fit(g)
    assert r.profile is None
    (rb,) = fresh_engine().fit_many([g])
    assert rb.profile is None


def test_profile_config_validation():
    with pytest.raises(ValueError):
        EngineConfig(profile="everything")
    # profile joins the compile key: off/on builds are distinct
    assert EngineConfig(profile="off").algo_key() \
        != EngineConfig(profile="convergence").algo_key()


# --- stats() key parity: the legacy dicts survive the migration ---

def test_engine_stats_keys_and_registry_mirror():
    g = karate_club()[0]
    eng = fresh_engine()
    before = set(eng.stats())
    eng.fit(g)
    eng.fit(g)
    st = eng.stats()
    assert set(st) == before
    snap = REGISTRY.snapshot()
    fits = [v for k, v in snap.items()
            if k.startswith("engine") and k.endswith(".fits")]
    assert any(v >= 2 for v in fits)


def test_microbatcher_stats_keys_and_mirror():
    from repro.launch.microbatch import MicroBatcher
    g = karate_club()[0]
    eng = fresh_engine()
    with MicroBatcher(eng, max_batch=4) as mb:
        label = mb._obs.label
        [s.result() for s in [mb.submit(g) for _ in range(3)]]
        st = mb.stats()
        assert set(st) == {"requests", "batches", "batch_size_hist",
                           "mean_batch", "p50_ms", "p95_ms", "mean_ms"}
        assert st["requests"] == 3
        snap = REGISTRY.snapshot()
        assert snap[f"{label}.requests"] == 3
        assert snap[f"{label}.batches"] == st["batches"]
        assert snap[f"{label}.latency_ms"]["count"] == 3
    # close() released the standalone batcher's scope
    assert f"{label}.requests" not in REGISTRY.snapshot()


def test_admission_stats_keys_and_mirror():
    from repro.serve.admission import AdmissionQueue
    reg = MetricsRegistry()
    q = AdmissionQueue(4, scope=reg.scope("adm"))
    q.offer("a", 1)
    q.offer("b", 2)
    assert q.take() is not None
    st = q.stats()
    assert set(st) == {"capacity", "depth", "peak_depth", "accepted",
                       "rejected", "held", "tenants_queued",
                       "served_per_tenant"}
    snap = reg.snapshot()
    assert snap["adm.accepted"] == st["accepted"] == 2
    assert snap["adm.taken"] == 1
    assert snap["adm.depth"] == st["depth"] == 1
    assert snap["adm.held"] == st["held"] == 1


def test_slice_loader_and_ledger_stats_keys_and_mirror():
    from repro.partition.ooc import _OOC, fit_out_of_core, open_source
    g = erdos_renyi(150, 5.0, seed=3)
    run = fit_out_of_core(open_source(g), EngineConfig(split="lp"),
                          memory_budget="1MB", num_partitions=2)
    assert {"partitions", "partition_loads", "prefetches",
            "peak_resident_bytes"} <= set(run.stats())
    snap = REGISTRY.snapshot()
    label = _OOC.label
    assert snap[f"{label}.fits"] >= 1
    assert snap[f"{label}.loads"] >= run.partition_loads > 0
    assert snap[f"{label}.requests"] >= snap[f"{label}.loads"]
    assert snap[f"{label}.bytes_peak"] > 0
    assert snap[f"{label}.exchange_bytes"] >= run.exchange_bytes > 0


def test_ledger_standalone_unscoped():
    from repro.partition.slices import MemoryLedger
    led = MemoryLedger(1000)            # no scope: raw construction works
    led.acquire(600, "a")
    assert led.stats() == {"budget": 1000, "current": 600, "peak": 600}
    led.release(600)


def test_service_stats_keys_and_scope_release():
    from repro.serve.service import ServiceConfig, TenantService
    g = karate_club()[0]
    eng = fresh_engine()
    svc = TenantService(eng, ServiceConfig(queue_capacity=8))
    label = svc._obs.label
    svc.register("t0", g).result()
    st = svc.stats()
    assert {"tenants", "outstanding", "completed", "failed", "spills",
            "uncached", "restored", "warm_cached_tenants", "warm_bytes",
            "p50_ms", "p99_ms", "mean_ms", "admission",
            "batcher"} <= set(st)
    snap = REGISTRY.snapshot()
    assert snap[f"{label}.completed"] == st["completed"] == 1
    assert snap[f"{label}.tenants"] == 1
    assert f"{label}.admission.accepted" in snap
    assert f"{label}.batcher.requests" in snap
    assert f"{label}.warm.bytes_current" in snap
    svc.close()
    assert not [k for k in REGISTRY.snapshot()
                if k.startswith(f"{label}.") or k == label]


def test_serving_emits_spans():
    from repro.serve.service import ServiceConfig, TenantService
    g = karate_club()[0]
    TRACER.reset()
    with TenantService(fresh_engine(),
                       ServiceConfig(queue_capacity=8)) as svc:
        svc.register("t", g).result()
        svc.refresh("t").result()
    names = {s.name for s in TRACER.spans()}
    assert {"serve.admit", "serve.launch", "serve.settle",
            "batch.dispatch", "batch.settle"} <= names


@pytest.mark.parametrize("backend", BACKENDS)
def test_batcher_and_member_spans_nest(backend):
    """A batch's collection is a root span on the batcher's worker, before
    its dispatch; the members' loop nests under ``engine.fit_many``, which
    nests under ``batch.dispatch``.  Labels are bit-identical with the
    tracer off."""
    from repro.launch.microbatch import MicroBatcher
    graphs = [erdos_renyi(n, 4.0, seed=i) for i, n in enumerate((60, 90, 75))]

    def served():
        with MicroBatcher(fresh_engine(backend=backend), max_batch=3,
                          batch_timeout_ms=50, autostart=False) as mb:
            subs = [mb.submit(g) for g in graphs]
            mb.start()
            return [s.result(timeout=300).labels for s in subs]

    TRACER.reset()
    labels = served()
    by_name = {}
    for s in TRACER.spans():
        by_name.setdefault(s.name, []).append(s)
    (collect,) = by_name["batch.collect"]
    (dispatch,) = by_name["batch.dispatch"]
    (fit_many,) = by_name["engine.fit_many"]
    (members,) = by_name["engine.members"]
    assert collect.parent_id == 0 and collect.attrs == {"size": 3}
    assert collect.tid == dispatch.tid
    assert collect.t0 + collect.dur <= dispatch.t0
    assert fit_many.parent_id == dispatch.span_id
    assert members.parent_id == fit_many.span_id
    assert members.attrs == {"k": 3}
    compacts = [s for s in by_name["engine.compact"]
                if s.parent_id == members.span_id]
    assert len(compacts) == 3
    assert fit_many.t0 <= members.t0
    assert members.t0 + members.dur <= fit_many.t0 + fit_many.dur
    TRACER.enabled = False
    try:
        quiet = served()
    finally:
        TRACER.enabled = True
    assert all(np.array_equal(a, b) for a, b in zip(labels, quiet))


def test_scope_release_frees_child_labels():
    """Releasing a scope must free its children's labels too — a
    restarted service's sub-scopes get bare names, not #1 suffixes."""
    reg = MetricsRegistry()
    s1 = reg.scope("svc")
    assert s1.scope("inner").label == "svc.inner"
    s1.release()
    s2 = reg.scope("svc")
    assert s2.label == "svc"
    assert s2.scope("inner").label == "svc.inner"


# --- exporters: exemplars, prometheus text, endpoint, jsonl ---

def test_histogram_exemplars_capture_span_ids():
    reg = MetricsRegistry()
    h = reg.histogram("lat", (10, 100))
    h.observe(5)                       # outside any span: no exemplar
    assert h.exemplars() == [None, None, None]
    TRACER.reset()
    with TRACER.span("req") as s:
        h.observe(50)
        h.observe(500)                 # overflow bucket
    ex = h.exemplars()
    assert ex[0] is None
    assert ex[1] == (50.0, s.span_id)
    assert ex[2] == (500.0, s.span_id)
    # the latest observation in a bucket wins
    with TRACER.span("req2") as s2:
        h.observe(60)
    assert h.exemplars()[1] == (60.0, s2.span_id)


def test_prometheus_text_round_trip():
    from repro.obs import parse_prometheus_text, prometheus_text
    reg = MetricsRegistry()
    reg.counter("svc.requests").inc(3)
    reg.gauge("svc.quality.disconnected_fraction").set(0.0)
    h = reg.histogram("svc.lat_ms", (1, 10))
    TRACER.reset()
    with TRACER.span("s") as sp:
        h.observe(0.5)
        h.observe(7.0)
        h.observe(7.0)
    text = prometheus_text(reg)
    assert text.endswith("# EOF\n")
    parsed = parse_prometheus_text(text)
    assert parsed["repro_svc_requests_total"][0]["value"] == 3.0
    assert parsed["repro_svc_quality_disconnected_fraction"][0]["value"] \
        == 0.0
    buckets = parsed["repro_svc_lat_ms_bucket"]
    # cumulative counts, +Inf last
    assert [b["labels"]["le"] for b in buckets] == ["1", "10", "+Inf"]
    assert [b["value"] for b in buckets] == [1.0, 3.0, 3.0]
    # every observation ran inside a span: exemplars carry its id
    ex = buckets[1]["exemplar"]
    assert ex["labels"]["span_id"] == str(sp.span_id)
    assert ex["value"] == 7.0
    assert parsed["repro_svc_lat_ms_count"][0]["value"] == 3.0
    assert parsed["repro_svc_lat_ms_sum"][0]["value"] == \
        pytest.approx(14.5)


def test_prometheus_parser_is_strict():
    from repro.obs import parse_prometheus_text
    with pytest.raises(ValueError, match="EOF"):
        parse_prometheus_text("repro_x_total 1\n")
    with pytest.raises(ValueError, match="malformed sample"):
        parse_prometheus_text("not a metric line!\n# EOF\n")
    with pytest.raises(ValueError, match="after # EOF"):
        parse_prometheus_text("# EOF\nrepro_x_total 1\n")
    with pytest.raises(ValueError, match="malformed comment"):
        parse_prometheus_text("# FREeform chatter\n# EOF\n")


def test_metrics_server_routes():
    import urllib.request

    from repro.obs import MetricsServer, parse_prometheus_text
    reg = MetricsRegistry()
    reg.counter("hits").inc(2)
    with MetricsServer(reg, port=0,
                       health_fn=lambda: {"tenants": 3}) as srv:
        def get(path):
            with urllib.request.urlopen(srv.url + path, timeout=10) as r:
                return r.headers.get("Content-Type"), r.read().decode()

        ctype, text = get("/metrics")
        assert ctype.startswith("text/plain")
        assert parse_prometheus_text(text)["repro_hits_total"][0][
            "value"] == 2.0
        _, js = get("/metrics.json")
        assert json.loads(js)["hits"] == 2
        _, hz = get("/healthz")
        assert json.loads(hz) == {"ok": True, "tenants": 3}
        reg.counter("hits").inc()      # scrapes render live values
        _, text2 = get("/metrics")
        assert parse_prometheus_text(text2)["repro_hits_total"][0][
            "value"] == 3.0
        with pytest.raises(urllib.error.HTTPError):
            get("/nope")


def test_jsonl_sink_appends_snapshots(tmp_path):
    from repro.obs import JsonlSink
    reg = MetricsRegistry()
    reg.counter("n").inc()
    path = tmp_path / "metrics.jsonl"
    with JsonlSink(str(path)) as sink:
        sink.emit(reg, tag="t+1s")
        reg.counter("n").inc()
        sink.emit(reg, tag="shutdown")
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [l["tag"] for l in lines] == ["t+1s", "shutdown"]
    assert lines[0]["metrics"]["n"] == 1
    assert lines[1]["metrics"]["n"] == 2
    assert lines[1]["ts"] >= lines[0]["ts"]


# --- ooc chrome trace / stats reporter / obs top ---

def test_ooc_chrome_trace_export(tmp_path):
    g = erdos_renyi(150, 5.0, seed=9)
    TRACER.reset()
    r = fresh_engine(split="lp").fit(g, memory_budget="4KB")
    assert r.partitions > 1
    names = {s.name for s in TRACER.spans()}
    assert {"ooc.plan", "ooc.propagation", "ooc.split"} <= names
    out = tmp_path / "ooc_trace.json"
    n = TRACER.export_chrome(out)
    events = json.loads(out.read_text())
    assert n == len(events) >= 3
    ooc_events = [e for e in events if e["name"].startswith("ooc.")]
    assert {e["name"] for e in ooc_events} \
        >= {"ooc.plan", "ooc.propagation", "ooc.split"}
    for ev in ooc_events:
        assert ev["ph"] == "X" and ev["dur"] >= 0


def test_periodic_stats_reporter_flushes_quality(tmp_path, capsys):
    """The serve driver's --stats-every-s reporter: periodic ticks while
    the workload runs, and a final flush on shutdown that carries the
    quality gauges the run populated (plus the JSONL mirror)."""
    import time as _time

    from repro.launch.serve import _PeriodicStats
    from repro.obs import JsonlSink
    g = karate_club()[0]
    path = tmp_path / "stats.jsonl"
    sink = JsonlSink(str(path))
    with _PeriodicStats(0.05, sink=sink):
        eng = fresh_engine(quality="full")
        label = eng._q_obs.label
        eng.fit(g)
        _time.sleep(0.15)              # let at least one tick fire
    sink.emit(tag="shutdown")
    sink.close()
    out = capsys.readouterr().out
    assert "[stats t+" in out          # periodic snapshot emitted
    assert "[stats final]" in out
    final = out.split("[stats final]")[1]
    assert f"{label}.disconnected_fraction" in final
    assert f"{label}.modularity" in final
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines[-1]["tag"] == "shutdown"
    assert lines[-1]["metrics"][f"{label}.disconnected_fraction"] == 0.0
    assert any(l["tag"] == "final" for l in lines)


def test_obs_top_renders_frames():
    from repro.launch.obs import render_top, run_top
    reg = MetricsRegistry()
    reg.counter("svc.requests").inc(7)
    reg.histogram("svc.lat_ms", (1, 10)).observe(3.0)
    frame = render_top(reg.snapshot(), limit=1)
    assert "metric" in frame and "... 1 more metrics" in frame
    outputs = []
    frames = run_top(every_s=0.0, iterations=2, registry=reg,
                     out=outputs.append)
    assert frames == 2
    joined = "\n".join(outputs)
    assert "svc.requests" in joined and "svc.lat_ms" in joined
    assert "[obs top] frame 2" in joined


def test_obs_top_polls_endpoint():
    from repro.launch.obs import run_top
    from repro.obs import MetricsServer
    reg = MetricsRegistry()
    reg.counter("polls").inc(5)
    outputs = []
    with MetricsServer(reg, port=0) as srv:
        frames = run_top(endpoint=srv.url, every_s=0.0, iterations=1,
                         out=outputs.append)
    assert frames == 1
    assert any("polls" in line for line in outputs)
