"""From a profiler trace to the numbers the per-layer metrics read.

A traced run wraps its measured window in the host annotation
``bench.window`` and every call it makes into the system in annotations of
its own (``bench.fit``, ``bench.submit``, ``bench.settle``, ...).  The
reduction reads the ``.xplane.pb`` that ``jax.profiler`` wrote:

* device operations are the events on the ``XLA Ops`` line (a prefix) of
  each plane named ``/device:TPU:<i>``; control-flow operations (``while``,
  ``conditional``, ``call``) span the operations they run and are left out
  of the per-operation breakdown, not out of the busy time;
* busy time is the union of a device's operation intervals inside the
  window, averaged over the devices that ran anything;
* a program's device time is the union of the intervals of its executions
  on the ``XLA Modules`` line (``jit_<function>``), clipped to the window
  and averaged the same way;
* an idle gap is a stretch of the window with no operation on the device;
  it is attributed to the innermost host span (the benchmark's annotations
  and the Python functions the profiler records) around its midpoint.

``reduce_trace`` takes the plane and line names as arguments, so a trace
recorded on the CPU can stand in for a chip's in the tests.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"
DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_LINE = "python3"
CONTROL_FLOW = ("while", "conditional", "call")


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # union of device ops, per device
    devices: int
    op_seconds: dict = field(default_factory=dict)     # label -> s/device
    idle_by_host: dict = field(default_factory=dict)   # host span -> s
    ops: list = field(default_factory=list)            # device op Events
    modules: list = field(default_factory=list)        # clipped to window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        def best(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                    [:top]]
        return {"device_ops": best(self.op_seconds),
                "idle_gaps": best(self.idle_by_host)}


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load_events(path: str) -> list[Event]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def short_op(name: str) -> str:
    """``%fusion.25 = pred[8388608]{...} fusion(...)`` -> ``fusion.25``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def op_label(name: str) -> str:
    """An op's name and its result's type, without layout."""
    head, _, rest = name.partition(" = ")
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%").strip() + (f" {shape.group(1)}" if shape else "")


def module_name(name: str) -> str:
    """``jit__propagate(123)`` -> ``jit__propagate``."""
    return re.sub(r"\(\d+\)$", "", name)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


class _SpanIndex:
    """Host spans binned by time, for "innermost span around t" queries."""

    BIN_NS = 1e6

    def __init__(self, spans):
        self.bins = defaultdict(list)
        for s in spans:
            for b in range(int(s.start_ns // self.BIN_NS),
                           int(s.end_ns // self.BIN_NS) + 1):
                self.bins[b].append(s)

    def innermost(self, t):
        inside = [s for s in self.bins.get(int(t // self.BIN_NS), ())
                  if s.start_ns <= t <= s.end_ns]
        return min(inside, key=lambda s: s.dur_ns) if inside else None


def reduce_trace(events: list[Event], window: str = WINDOW,
                 device_plane: str = DEVICE_PLANE, op_line: str = OP_LINE,
                 module_line: str = MODULE_LINE,
                 host_plane: str = HOST_PLANE,
                 host_line: str = HOST_LINE) -> TraceSummary:
    wins = [e for e in events if e.name == window]
    if not wins:
        raise ValueError(f"no {window!r} span in the trace")
    w0, w1 = wins[0].start_ns, wins[0].end_ns
    ops = [e for e in events if e.plane.startswith(device_plane)
           and e.line.startswith(op_line) and e.end_ns > w0
           and e.start_ns < w1]
    by_dev = defaultdict(list)
    for e in ops:
        by_dev[e.plane].append((max(e.start_ns, w0), min(e.end_ns, w1)))
    devices = max(len(by_dev), 1)
    busy = {p: _union(iv) for p, iv in by_dev.items()}
    busy_ns = sum(e - s for iv in busy.values() for s, e in iv) / devices

    modules = sorted((e for e in events if e.plane.startswith(device_plane)
                      and e.line == module_line), key=lambda e: e.start_ns)
    mod_starts = [m.start_ns for m in modules]
    op_ns = defaultdict(float)
    for e in ops:
        if short_op(e.name).split(".")[0] in CONTROL_FLOW:
            continue
        i = bisect.bisect_right(mod_starts, e.start_ns) - 1
        mod = ""
        if i >= 0 and modules[i].end_ns >= e.start_ns:
            mod = module_name(modules[i].name) + ":"
        op_ns[mod + op_label(e.name)] += min(e.end_ns, w1) - max(e.start_ns,
                                                                 w0)

    host = _SpanIndex(e for e in events if e.plane == host_plane
                      and e.line == host_line and e.dur_ns > 0
                      and e.name != window and e.end_ns > w0
                      and e.start_ns < w1 and e.dur_ns < w1 - w0)
    idle = defaultdict(float)
    for iv in busy.values() or [[]]:
        edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                span = host.innermost((s + e) / 2)
                idle[span.name if span else "no host span"] += e - s
    clipped = []
    for m in modules:
        s, e = max(m.start_ns, w0), min(m.end_ns, w1)
        if e > s:
            clipped.append(Event(m.plane, m.line, module_name(m.name), s,
                                 e - s))
    return TraceSummary(
        window_s=(w1 - w0) / 1e9, busy_s=busy_ns / 1e9, devices=devices,
        op_seconds={k: v / 1e9 / devices for k, v in op_ns.items()},
        idle_by_host={k: v / 1e9 / devices for k, v in idle.items()},
        ops=ops, modules=clipped)


def kernel_calls(summary: TraceSummary, kernel: str):
    """Events of one Pallas kernel: ops named ``kernel`` or ``kernel.<n>``."""
    pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    return [e for e in summary.ops if pat.match(short_op(e.name))]


def tile_shape(op_name: str) -> tuple[int, int] | None:
    """The largest 2-D operand shape in an op's text: its (rows, d) tile."""
    rest = op_name.split(" = ", 1)[-1]
    shapes = [(int(a), int(b)) for a, b in
              re.findall(r"\[(\d+),(\d+)\]", rest)]
    return max(shapes, key=lambda s: s[0] * s[1]) if shapes else None


def module_seconds(summary: TraceSummary, prefixes: tuple[str, ...]) -> float:
    """Device seconds of the programs whose names start with one of
    ``prefixes``, inside the window, per device."""
    by_dev = defaultdict(list)
    for m in summary.modules:
        if m.name.startswith(prefixes):
            by_dev[m.plane].append((m.start_ns, m.end_ns))
    ns = sum(e - s for iv in by_dev.values() for s, e in _union(iv))
    return ns / 1e9 / summary.devices
