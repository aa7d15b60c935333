"""Whole runs of tiny cells on the CPU, with and without faults.

The harness refuses the CPU in a real run; these tests pass
``allow_cpu=True`` to ``harness.execute`` to drive the rest of a run --
set-up, window, reference comparison and the result line -- on tiny copies
of the cells that live in a temporary directory.  The whole-run tests take
one case for each cell of ``BENCHMARK.json`` (its tiny copy, from
``tests/tiny/<cell>.json``); the fault tests name the tiny cells they plant
faults in.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest
from test_bench_files import (check_benchmark, check_cell, check_config,
                              check_metric)
from tiny_cells import BENCH, BENCHMARK, ROOT, run_tiny, tiny_names

from lpabench import graphs, harness, spec

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    # no chip to pin on the CPU, and JAX's process-wide compile cache is
    # left alone; generated graphs go to the test's directory
    monkeypatch.setattr(harness, "pin_chips", lambda chips: None)
    monkeypatch.setattr(harness, "configure_jax", lambda: None)
    monkeypatch.setattr(graphs, "CACHE", tmp_path / "graphs")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")


@pytest.fixture
def fresh_plans(monkeypatch):
    """Plans built after a fault is planted, not taken from earlier tests."""
    import repro.engine.engine as engine_mod
    from repro.engine import CompileCache
    monkeypatch.setattr(engine_mod, "GLOBAL_CACHE", CompileCache())


def _window_info(out: str) -> dict:
    return next(d for d in (json.loads(line) for line in out.splitlines()
                            if line.startswith('{"info"'))
                if d["info"] == "window")


@pytest.mark.parametrize("cell", tiny_names())
def test_untraced_run_line(tmp_path, cell, capsys):
    res = run_tiny(tmp_path / "b", cell)
    tiny = spec.load_cell(cell, tmp_path / "b", {})
    assert _window_info(capsys.readouterr().out)["params"] == \
        tiny.config["params"]
    assert list(res)[:5] == KEYS and list(res)[-1] == "checks"
    assert set(res) == set(KEYS) | {"checks"}
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert "setup_s" in res["metrics"]
    assert len(res["metrics"]) == 2
    assert set(res["device"]) == {"platform", "kind", "device_kind",
                                  "count", "memory_peak_bytes"}
    assert res["device"]["kind"] == res["device"]["device_kind"]
    assert res["checks"]["label_mismatch_vertices"] == {"value": 0,
                                                        "limit": 0}
    json.dumps(res)


@pytest.mark.parametrize("cell", tiny_names())
def test_traced_run_line(tmp_path, cell):
    res = run_tiny(tmp_path / "b", cell, trace=True)
    assert list(res) == KEYS + ["breakdown", "checks"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "setup_s" not in res["metrics"] and res["metrics"]
    assert res["correct"] is True


def test_answer_altered_where_produced(tmp_path, monkeypatch):
    import repro.engine.engine as engine_mod
    real = engine_mod._compact_host

    def altered(labels):
        out, k = real(labels)
        out = out.copy()
        out[0] = (out[0] + 1) % max(k, 2)
        return out, k
    monkeypatch.setattr(engine_mod, "_compact_host", altered)
    res = run_tiny(tmp_path / "b", "tiny-road.oneshot")
    assert res["correct"] is False and res["failed"] == res["attempted"]
    assert res["checks"]["label_mismatch_vertices"]["value"] > 0


def test_step_returns_state_unchanged(tmp_path, monkeypatch, fresh_plans):
    import repro.engine.backends.segment as segment
    real = segment.lpa_run

    def stuck(graph, **kw):
        return real(graph, **{**kw, "max_iterations": 0})
    monkeypatch.setattr(segment, "lpa_run", stuck)
    res = run_tiny(tmp_path / "b", "tiny-g500.oneshot")
    assert res["correct"] is False
    assert res["checks"]["label_mismatch_vertices"]["value"] > 0


def _oneshot(tmp_path, cell, seconds=0.0):
    import time

    from lpabench import spec
    from tiny_cells import tiny_bench
    bench_dir, benchmark = tiny_bench(tmp_path / "b")
    c = spec.load_cell(cell, bench_dir, benchmark)
    run = harness.Run(cell=c, seed=2**31 + 29, seconds=seconds, trace=False,
                      t_process=time.perf_counter())
    return run, spec.traffic_module(c)


def test_graphs_of_a_cell_come_from_the_seed(tmp_path, monkeypatch):
    run, traffic = _oneshot(tmp_path, "tiny-road.oneshot")
    count = run.cell.traffic["graphs"]
    first = traffic.make_inputs(run)
    monkeypatch.setattr(graphs, "CACHE", tmp_path / "other")  # made anew
    again = traffic.make_inputs(run)
    assert len(first) == len(again) == count > 1
    for (n1, e1, _), (n2, e2, _) in zip(first, again):
        assert n1 == n2 and len(e1) == len(e2) and (e1 == e2).all()
    assert len({e.tobytes() for _, e, _ in first}) == count
    assert len({(n, len(e)) for n, e, _ in first}) == 1


def test_fits_take_the_graphs_in_turn(tmp_path, monkeypatch):
    from types import SimpleNamespace
    run, traffic = _oneshot(tmp_path, "tiny-road.oneshot", seconds=10.0)
    state = traffic.setup(run)
    assert len(state["graphs"]) == run.cell.traffic["graphs"]
    state["graphs"] = state["graphs"][:2]
    # the window's start, then each fit's start and end: it closes in fit 3
    clock = iter([0.0, 0.0, 1.0, 1.0, 2.0, 2.25, 20.0])
    monkeypatch.setattr(traffic, "time",
                        SimpleNamespace(perf_counter=lambda: next(clock)))
    win = traffic.window(run, state)
    assert win.info["graph_of_fit"] == [0, 1, 0]
    edges = [state["graphs"][i].num_edges for i in (0, 1, 0)]
    assert win.info["fit_edges"] == edges
    assert win.end_to_end["edges_per_s"] == sum(edges) / 20.0
    assert (win.info["fit_s_median"], win.info["fit_s_max"],
            win.info["fit_gap_s_max"]) == (1.0, 17.75, 0.25)
    assert win.info["fit_s"] == [1.0, 1.0, 17.75]


def test_window_times_its_fits(tmp_path):
    run, traffic = _oneshot(tmp_path, "tiny-road.oneshot", seconds=0.5)
    info = traffic.window(run, traffic.setup(run)).info
    assert 0 < info["fit_s_median"] <= info["fit_s_max"] <= info["window_s"]
    assert 0 <= info["fit_gap_s_max"] < info["window_s"]
    assert info["fit_s_median"] * info["fits"] <= info["window_s"] * 2
    assert info["fit_s_max"] + info["fit_gap_s_max"] <= info["window_s"]
    assert info["window_s"] >= 0.5
    assert set(info["fit_max_timings"]) >= {"prepare", "compact"}
    assert sum(info["fit_max_timings"].values()) <= info["fit_s_max"]
    assert len(info["fit_s"]) == info["fits"]
    assert max(info["fit_s"]) == info["fit_s_max"]
    assert sum(info["fit_s"]) + info["fit_gap_s_max"] <= info["window_s"]
    assert set(info["stage_s_median"]) == set(info["fit_max_timings"])
    assert sum(info["stage_s_median"].values()) <= info["fit_s_max"]


def test_each_fit_is_held_to_its_own_graph(tmp_path):
    run, traffic = _oneshot(tmp_path, "tiny-road.oneshot")
    state = traffic.setup(run)
    engine, built = state["engine"], state["graphs"]
    win = traffic.window(run, state)
    win.records = [engine.fit(built[i]) for i in (0, 1)]
    win.info["graph_of_fit"] = [0, 1]
    checks, failed = traffic.check(run, dict(state), win)
    assert failed == 0 and checks[0].value == 0
    inputs = state["inputs"]
    state["inputs"] = [inputs[1], inputs[0]] + inputs[2:]
    checks, failed = traffic.check(run, state, win)
    assert failed == 2 and checks[0].value > 0


def test_no_tpu_exits_nonzero_without_a_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "road-256.oneshot", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_a_cell_is_new_files(tmp_path, capsys):
    """A cell made of new files alone -- a workload that sets its
    configuration's ``side`` through ``params``, its tiny file and its
    entries in ``BENCHMARK.json`` -- loads, passes the file checks and runs
    tiny and correct, in a copy of the checkout."""
    src = tmp_path / "checkout"
    shutil.copytree(BENCH, src / "bench", ignore=shutil.ignore_patterns(
        ".cache", "__pycache__"))
    shutil.copy(ROOT / "PERF.md", src)
    benchmark = json.loads(json.dumps(BENCHMARK))
    cfg = next(c for c in benchmark["configs"] if "side" in c["reduced"])
    like = next(w for w in benchmark["workloads"]
                if w["config"] == cfg["name"])
    cell = "side-24.oneshot"
    why = "two 24x24 graphs: a cell added as files"
    entry = {"name": cell, "config": cfg["name"], "traffic": "oneshot",
             "chips": 1, "why": why}
    (src / "bench" / "workloads" / f"{cell}.json").write_text(json.dumps(
        {"config": cfg["name"], "traffic": {"kind": "oneshot", "graphs": 2},
         "chips": 1, "params": {"side": 24}, "why": why}))
    (src / "bench" / "tests" / "tiny" / f"{cell}.json").write_text(
        json.dumps({"name": "tiny-side-24.oneshot"}))
    benchmark["workloads"].append(entry)
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if like["name"] in m.get("workloads", ()):
            m["workloads"].append(cell)
    (src / "BENCHMARK.json").write_text(json.dumps(benchmark))

    c = check_cell(entry, src / "bench", benchmark)
    raw = json.loads((ROOT / cfg["file"]).read_text())
    assert c.config["params"] == {**raw["params"], "side": 24}
    for config in benchmark["configs"]:
        check_config(config, src)
    for metric in benchmark["per_layer"]:
        check_metric(metric, src / "bench", benchmark)
    check_benchmark(benchmark, src)

    res = run_tiny(tmp_path / "b", "tiny-side-24.oneshot",
                   source=src / "bench", benchmark=benchmark)
    assert res["correct"] is True
    assert res["checks"]["label_mismatch_vertices"] == {"value": 0,
                                                        "limit": 0}
    info = _window_info(capsys.readouterr().out)
    assert info["params"]["side"] == 24 and info["fit_n"][0] == 24 * 24
    assert not (BENCH / "workloads" / f"{cell}.json").exists()
    assert not (BENCH / "tests" / "tiny" / f"{cell}.json").exists()
