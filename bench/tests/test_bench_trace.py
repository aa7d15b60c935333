"""The reduction from a profiler trace to metrics, on a recorded trace.

``fixtures/cpu_window.xplane.pb`` was recorded on the CPU: inside a
``bench.window`` annotation, three rounds of a jitted 512x512 matmul with
its sum (under ``bench.fit``) followed by a 20 ms sleep (under
``bench.host``).  On the CPU the XLA operations run on the
``tf_XLAPjRtCpuClient`` thread of ``/host:CPU``; the reduction is pointed
there in place of a chip's ``/device:TPU:<i>`` plane and ``XLA Ops`` line.
"""
from __future__ import annotations

from types import SimpleNamespace

import pytest
from tiny_cells import BENCH

from lpabench import bytemodel, peaks, spec, tracing

FIXTURE = BENCH / "tests" / "fixtures" / "cpu_window.xplane.pb"
V5E = peaks.peaks_for("TPU v5 lite")


@pytest.fixture(scope="module")
def summary():
    events = tracing.load_events(str(FIXTURE))
    return tracing.reduce_trace(events, device_plane="/host:CPU",
                                op_line="tf_XLAPjRtCpuClient")


def test_window_and_busy_time(summary):
    assert summary.window_s == pytest.approx(0.073494713, rel=1e-6)
    # three matmuls of ~4 ms and their reductions; the sleeps are idle
    assert 0.0115 < summary.busy_s < 0.0130
    assert summary.idle_share == pytest.approx(0.8332, abs=1e-3)


def test_idle_gaps_name_the_host_span(summary):
    gaps = summary.breakdown()["idle_gaps"]
    assert gaps[0][0] == "$time sleep"
    assert sum(s for _, s in gaps) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)


def test_device_ops_breakdown(summary):
    ops = summary.breakdown()["device_ops"]
    assert ops[0][0] == "dot_general.1"
    assert ops[0][1] == pytest.approx(0.01156, rel=1e-3)
    assert len(ops) <= 10


def test_idle_share_reader(summary):
    reader = spec.metric_reader(BENCH, "device_idle_share.oneshot")
    assert reader.read(None, None, summary) == pytest.approx(83.32, abs=0.1)
    assert reader.read(None, None, None) is None


def _tpu_op(name, text, start, dur):
    return tracing.Event("/device:TPU:0", "XLA Ops", f"%{name} = {text}",
                         start, dur)


MOVE = ("(s32[65536,1]{1,0:T(8,128)}, s32[65536,1]{1,0:T(8,128)}) "
        "custom-call(s32[1,1]{1,0:T(1,128)} %b.35, "
        "s32[65536,128]{1,0:T(8,128)S(1)} %f.1)")
SPLIT = ("s32[65536,1]{1,0:T(8,128)S(1)} custom-call("
         "s32[65536,128]{1,0:T(8,128)S(1)} %b.25, "
         "s32[65536,1]{1,0:T(8,128)} %c.4)")


# as recorded on a TPU v5e (road-256.oneshot): the outputs are
# (65536, 1) columns, the operand tiles (65536, 8)
MOVE_W8 = (
    "(s32[65536,1]{1,0:T(8,128)S(1)}, s32[65536,1]{1,0:T(8,128)}) "
    "custom-call(s32[1,1]{1,0:T(1,128)} %bitcast.19, "
    "s32[65536,8]{1,0:T(8,128)} %reshape.130, "
    "f32[65536,8]{1,0:T(8,128)} %get-tuple-element.315, "
    "s32[65536,8]{1,0:T(8,128)S(1)} %copy-done.9, "
    "s32[65536,8]{1,0:T(8,128)} %reshape.131, "
    "s32[65536,1]{1,0:T(8,128)} %copy.14, s32[65536,1]{1,0:T(8,128)} "
    "%copy.15, s32[65536,1]{1,0:T(8,128)} %copy.16, "
    "s32[65536,1]{1,0:T(8,128)} %get-tuple-element.320, "
    "s32[65536,1]{1,0:T(8,128)S(1)} %custom-call.17), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    "{s32[1,1]{1,0}, s32[65536,8]{1,0}, f32[65536,8]{1,0}, "
    "s32[65536,8]{1,0}, s32[65536,8]{1,0}, s32[65536,1]{1,0}, "
    "s32[65536,1]{1,0}, s32[65536,1]{1,0}, s32[65536,1]{1,0}, "
    "s32[65536,1]{1,0}}, frontend_attributes={kernel_metadata={}}")
SPLIT_W8 = (
    "s32[65536,1]{1,0:T(8,128)S(1)} custom-call("
    "s32[65536,8]{1,0:T(8,128)} %reshape.38, "
    "s32[65536,8]{1,0:T(8,128)} %reshape.39, "
    "s32[65536,8]{1,0:T(8,128)} %convert_element_type.9, "
    "s32[65536,1]{1,0:T(8,128)S(1)} %copy-done.8, "
    "s32[65536,1]{1,0:T(8,128)} %copy.8), "
    'custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("text", [MOVE_W8, SPLIT_W8], ids=["move", "split"])
def test_tile_shape_takes_the_operand_tile_not_the_output_column(text):
    assert tracing.tile_shape(text) == (65536, 8)


def _kernel_trace(move_ns, split_ns=4e5, move=MOVE, split=SPLIT):
    events = [tracing.Event("/host:CPU", "python3", "bench.window", 0, 1e9),
              _tpu_op("fused_move", move, 1e6, move_ns),
              _tpu_op("fused_move.1", move, 2e7, move_ns),
              _tpu_op("fused_split.4", split, 4e7, split_ns),
              _tpu_op("fusion.25", "pred[8388608]{0} fusion()", 5e7, 8e7)]
    return tracing.reduce_trace(events)


def test_kernel_calls_and_shapes():
    s = _kernel_trace(5.5e6)
    assert [tracing.short_op(e.name)
            for e in tracing.kernel_calls(s, "fused_move")] == \
        ["fused_move", "fused_move.1"]
    assert tracing.tile_shape(MOVE) == (65536, 128)
    assert tracing.tile_shape("s32[] constant()") is None


def test_kernel_roofline_reads_the_shapes():
    run = SimpleNamespace(peaks=V5E)
    reader = spec.metric_reader(BENCH, "kernel_roofline.road")
    share = reader.read(run, None, _kernel_trace(5.5e6))
    move_b, _ = bytemodel.fused_move_call(65536, 128)
    split_b, _ = bytemodel.fused_split_call(65536, 128)
    want = 100 * (2 * move_b + split_b) / V5E["hbm_bytes_per_s"] / (
        (2 * 5.5e6 + 4e5) / 1e9)
    assert share == pytest.approx(want)
    assert 1.0 < share < 3.0


def test_kernel_roofline_reads_width_8_tiles():
    # calls as long as on the chip: 0.935 ms a move, 0.19 ms a split
    run = SimpleNamespace(peaks=V5E)
    reader = spec.metric_reader(BENCH, "kernel_roofline.road")
    share = reader.read(run, None, _kernel_trace(9.35e5, 1.9e5, MOVE_W8,
                                                 SPLIT_W8))
    move_b, _ = bytemodel.fused_move_call(65536, 8)
    split_b, _ = bytemodel.fused_split_call(65536, 8)
    want = 100 * (2 * move_b + split_b) / V5E["hbm_bytes_per_s"] / (
        (2 * 9.35e5 + 1.9e5) / 1e9)
    assert share == pytest.approx(want)
    assert 1.0 < share < 1.5


def test_kernel_roofline_is_never_clamped():
    # a kernel that took less time than its bytes need at peak: the model
    # or the timing is wrong, and the reading says so instead of 100
    run = SimpleNamespace(peaks=V5E)
    reader = spec.metric_reader(BENCH, "kernel_roofline.road")
    assert reader.read(run, None, _kernel_trace(1e4, 1e4)) > 100.0


def test_no_kernel_no_reading():
    run = SimpleNamespace(peaks=V5E)
    events = [tracing.Event("/host:CPU", "python3", "bench.window", 0, 1e9),
              _tpu_op("fusion.1", "s32[8]{0} fusion()", 10, 10)]
    reader = spec.metric_reader(BENCH, "kernel_roofline.road")
    assert reader.read(run, None, tracing.reduce_trace(events)) is None


def _module(name, start, dur):
    return tracing.Event("/device:TPU:0", "XLA Modules", name, start, dur)


def _sweep_trace(propagate, split, scale=1.0):
    """A window of 1 s: propagation 0.3 s, split 0.2 s, a propagation that
    runs 0.05 s past the close, and a program the reader leaves out."""
    return tracing.reduce_trace([
        tracing.Event("/host:CPU", "python3", "bench.window", 0, 1e9),
        _tpu_op("fusion.1", "s32[8]{0} fusion()", 1e8, 1e6),
        _module(f"{propagate}(7)", 1e8, 3e8 * scale),
        _module(f"{split}(9)", 5e8, 2e8 * scale),
        _module("jit__compact(3)", 8e8, 1e8),
        _module(f"{propagate}(7)", 9.5e8, 1e8 * scale)])


SWEEP_WIN = SimpleNamespace(
    info={"fit_edges": [140_000] * 2, "fit_n": [65_536] * 2},
    records=[SimpleNamespace(lpa_iterations=9, split_iterations=11)] * 2)


@pytest.mark.parametrize("propagate,split", [
    ("jit__propagate", "jit__split"),              # segment
    ("jit__propagate_fused", "jit__split_fused")])  # tile
def test_sweep_roofline_reads_program_time(propagate, split):
    summary = _sweep_trace(propagate, split)
    assert tracing.module_seconds(
        summary, ("jit__propagate", "jit__split")) == pytest.approx(0.55)
    reader = spec.metric_reader(BENCH, "sweep_roofline.oneshot")
    want = 100 * 2 * bytemodel.sweep_bytes(140_000, 65_536, 20) / \
        V5E["hbm_bytes_per_s"] / 0.55
    assert reader.read(SimpleNamespace(peaks=V5E), SWEEP_WIN, summary) == \
        pytest.approx(want)


def test_sweep_roofline_is_never_clamped():
    summary = _sweep_trace("jit__propagate", "jit__split", scale=1e-6)
    reader = spec.metric_reader(BENCH, "sweep_roofline.oneshot")
    assert reader.read(SimpleNamespace(peaks=V5E), SWEEP_WIN, summary) > 100


def test_no_sweep_program_no_reading():
    reader = spec.metric_reader(BENCH, "sweep_roofline.oneshot")
    run = SimpleNamespace(peaks=V5E)
    assert reader.read(run, SWEEP_WIN, _sweep_trace("jit__a", "jit__b")) \
        is None
    assert reader.read(run, SWEEP_WIN, None) is None


def test_sweep_byte_model():
    assert bytemodel.sweep_bytes(m=100, n=10, sweeps=3) == (1200 + 80) * 3
    share = bytemodel.roofline_share(819e9, 0, 2.0, V5E)
    assert share == pytest.approx(50.0)
    assert bytemodel.roofline_share(819e9, 0, 0.5, V5E) == \
        pytest.approx(200.0)
    assert bytemodel.roofline_share(1, 0, 0.0, V5E) is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
