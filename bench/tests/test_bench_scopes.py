"""Device time by sweep scope and device-idle time inside the program's
spans, on synthetic traces shaped as a chip's, and on a recorded one.

On a chip, an ``XLA Ops`` event carries only its op's name and times; the
``XLA Modules`` line gives the program execution around it, and the
``/host:metadata`` plane each program's ``Hlo Proto``, whose instructions'
``op_name`` metadata hold the scope path.  The program's spans are host
annotations on ``/host:CPU``.
"""
from __future__ import annotations

import json
import shutil
from types import SimpleNamespace

import pytest
from tiny_cells import BENCH

from lpabench import harness, scopes, spec

MS = 1e6  # ns
FIXTURE = BENCH / "tests" / "fixtures" / "cpu_window.xplane.pb"
PROP = "jit__propagate_fused(12)"


def _ev(name, start_ms, dur_ms):
    return SimpleNamespace(name=name, start_ns=start_ms * MS,
                           duration_ns=dur_ms * MS, stats=[])


def _op(name, start_ms, dur_ms):
    return _ev(f"%{name} = s32[8]{{0}} fusion()", start_ms, dur_ms)


def _trace(ops, spans=(), modules=((PROP, 0, 45), ("jit__compact(3)", 48,
                                                    10))):
    """A window of 100 ms at 0..100 on the host's python3 line."""
    host = [_ev("bench.window", 0, 100), _ev("bench.fit", 0, 100)]
    host += [_ev(n, s, d) for n, s, d in spans]
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)  # noqa: E731
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[line("python3", host)]),
        SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Modules", [_ev(*m) for m in modules]),
            line("XLA Ops", list(ops))])])


BODY = "jit(_propagate_fused)/while/body"
OP_NAMES = {
    PROP: {
        "fusion.9": f"{BODY}/sweep.gather/gather",
        "fusion.25": f"{BODY}/sweep.gather/gather",
        "fused_move.10": f"{BODY}/sweep.reduce/jit(fused_move)/fused_move/"
                         "pallas_call",
        # innermost scope wins: a gather nested in the reduce
        "fusion.3": f"{BODY}/sweep.reduce/sweep.gather/gather",
        "fusion.4": f"{BODY}/sweep.wake/ne",
        "copy.1": f"{BODY}/copy",                         # no scope
        "while.2": "jit(_propagate_fused)/while",
        "fusion.6": f"{BODY}/sweep.wake/ne",
        "sort.1": f"{BODY}/sweep.sort/sort",
    },
    "jit__compact(3)": {"fusion.7": "jit(_compact)/sort"},
}
OPS = [
    _op("fusion.9", 10, 8), _op("fusion.25", 20, 6),
    _op("fused_move.10", 30, 2), _op("fusion.3", 33, 1),
    _op("fusion.4", 35, 3), _op("copy.1", 40, 1),
    _op("while.2", 5, 80),       # control flow: spans the ops it runs
    _op("fusion.9", 95, 10),     # runs past the close (no module: "")
    _op("fusion.6", -20, 5),     # before the open
    _op("fusion.7", 50, 4),      # another program
]


def _parse(ops, spans=(), op_names=OP_NAMES):
    return scopes.parse(_trace(ops, spans), op_names)


def test_scope_of_takes_the_innermost_sweep_component():
    assert scopes.scope_of(f"{BODY}/sweep.reduce/sweep.gather/gather") == \
        "sweep.gather"
    assert scopes.scope_of(f"{BODY}/sweep.wake/ne") == "sweep.wake"
    assert scopes.scope_of("jit(f)/while/body/add") == ""
    assert scopes.scope_of("") == ""


def test_ops_take_the_scope_of_their_program_instruction():
    t = _parse(OPS)
    assert [(o.module, o.name, o.scope) for o in t.ops[:4]] == [
        (PROP, "fusion.9", "sweep.gather"), (PROP, "fusion.25", "sweep.gather"),
        (PROP, "fused_move.10", "sweep.reduce"), (PROP, "fusion.3",
                                                  "sweep.gather")]
    got = t.scope_seconds()
    assert got["sweep.gather"] == pytest.approx((8 + 6 + 1) / 1e3)
    assert got["sweep.reduce"] == pytest.approx(2 / 1e3)
    assert got["sweep.wake"] == pytest.approx(3 / 1e3)
    # copy.1, the op past the close (outside any program) and the compaction
    assert got[""] == pytest.approx((1 + 5 + 4) / 1e3)
    assert "sweep.sort" not in got
    sweeps = t.scope_seconds(scopes.SWEEP_PROGRAMS)
    assert sweeps[""] == pytest.approx(1 / 1e3)


def test_unknown_program_gives_no_scope():
    t = _parse(OPS, op_names={})
    assert set(t.scope_seconds()) == {""}


def test_hlo_op_names_decode_a_recorded_trace():
    """The programs of a trace recorded on the CPU, from its
    ``/host:metadata`` plane, keyed as its modules are named."""
    names = scopes.hlo_op_names(FIXTURE.read_bytes())
    lam = next(v for k, v in names.items() if k.startswith("jit__lambda("))
    assert lam["dot_general.1"].endswith("dot_general")
    assert all(k.rstrip(")").split("(")[-1].isdigit() for k in names)


def _win(fits):
    return SimpleNamespace(records=[SimpleNamespace()] * fits,
                           info={"fit_edges": [1] * fits})


@pytest.fixture
def traced(monkeypatch):
    """Readers see the synthetic trace in place of a run's .xplane.pb."""
    def use(ops, spans=()):
        t = _parse(ops, spans)
        monkeypatch.setattr(scopes, "load", lambda run: t)
        return t
    return use


@pytest.mark.parametrize("metric,ms", [
    ("gather_ms.oneshot", 15.0), ("reduce_ms.oneshot", 2.0),
    ("wake_ms.oneshot", 3.0)])
def test_scope_readers_divide_per_fit(traced, metric, ms):
    traced(OPS)
    reader = spec.metric_reader(BENCH, metric)
    assert reader.read(None, _win(4), object()) == pytest.approx(ms / 4)
    assert reader.read(None, _win(4), None) is None


def test_missing_scope_reads_none_not_zero(traced):
    traced(OPS)  # a tile trace: no sort
    reader = spec.metric_reader(BENCH, "sort_ms.graph500")
    assert reader.read(None, _win(2), object()) is None
    traced([_op("sort.1", 10, 7)])
    assert reader.read(None, _win(2), object()) == pytest.approx(3.5)


def test_program_without_scopes_reads_none(traced):
    traced([_op("copy.1", 10, 5)])
    for metric in ("gather_ms.oneshot", "reduce_ms.oneshot",
                   "wake_ms.oneshot", "sort_ms.graph500"):
        reader = spec.metric_reader(BENCH, metric)
        assert reader.read(None, _win(1), object()) is None, metric


FRONT = [("engine.fit", 8, 60), ("engine.prepare", 10, 10),
         ("engine.propagate", 22, 20), ("engine.compact", 50, 2)]


def test_front_idle_reads_idle_time_inside_prepare_and_compact(traced):
    # busy 15..17 inside prepare, 40..51 over compact's first ms and the
    # propagation: prepare idles 8 ms, compact 1 ms
    traced([_op("fusion.9", 15, 2), _op("fusion.25", 40, 11)], FRONT)
    reader = spec.metric_reader(BENCH, "front_idle_ms.oneshot")
    assert reader.read(None, _win(3), object()) == pytest.approx(9 / 3)
    assert reader.read(None, _win(3), None) is None


def test_front_idle_without_device_ops_is_the_whole_span(traced):
    traced([], FRONT)
    reader = spec.metric_reader(BENCH, "front_idle_ms.oneshot")
    assert reader.read(None, _win(1), object()) == pytest.approx(12.0)


def test_front_idle_without_engine_spans_reads_none(traced):
    traced(OPS)  # a program that predates the spans
    reader = spec.metric_reader(BENCH, "front_idle_ms.oneshot")
    assert reader.read(None, _win(1), object()) is None


def test_slot_fill_reads_edges_over_slots():
    reader = spec.metric_reader(BENCH, "slot_fill.oneshot")
    road = SimpleNamespace(
        records=[SimpleNamespace(edge_slots=65536 * 128)] * 8,
        info={"fit_edges": [139_592] * 8})
    assert reader.read(None, road, None) == pytest.approx(1.664, abs=5e-4)
    mixed = SimpleNamespace(records=[SimpleNamespace(edge_slots=100),
                                     SimpleNamespace(edge_slots=300)],
                            info={"fit_edges": [90, 10]})
    assert reader.read(None, mixed, None) == pytest.approx(25.0)


def test_slot_fill_without_the_counter_reads_none():
    reader = spec.metric_reader(BENCH, "slot_fill.oneshot")
    older = SimpleNamespace(records=[SimpleNamespace()],
                            info={"fit_edges": [10]})
    assert reader.read(None, older, None) is None
    assert reader.read(None, SimpleNamespace(records=[], info={}),
                       None) is None


def test_load_reads_the_run_trace_once(tmp_path, monkeypatch):
    """The run's .xplane.pb is parsed once and kept for every reader."""
    run_dir = tmp_path / "c" / "plugins" / "profile" / "1"
    run_dir.mkdir(parents=True)
    shutil.copy(FIXTURE, run_dir / "x.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    calls = []
    real = scopes.parse
    monkeypatch.setattr(scopes, "parse",
                        lambda *a: calls.append(1) or real(*a))
    run = SimpleNamespace(cell=SimpleNamespace(name="c"))
    first = scopes.load(run)
    assert scopes.load(run) is first and calls == [1]
    # the CPU recording has no TPU plane: no scope, and no engine span
    assert first.scope_seconds() == {}
    assert first.idle_seconds_in(("engine.prepare",)) is None


def test_breakdown_command_prints_the_unscoped_share(tmp_path, monkeypatch,
                                                     capsys):
    """``python3 -m lpabench.scopes <dir>`` reports the sweep programs'
    device time by scope and the share in no scope."""
    monkeypatch.setattr(scopes.tracing, "newest_xplane", lambda d: d)
    monkeypatch.setattr(scopes, "read_xplane", lambda path: _parse(OPS))
    assert scopes.main([str(tmp_path)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["sweep_unscoped_share"] == pytest.approx(1 / 21)
    assert out["top_ops"][0][:2] == [f"{PROP}:fusion.9", "sweep.gather"]
