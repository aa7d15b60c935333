"""Device time by sweep phase, and device-idle time inside the program's spans.

The program names the operations of its sweep bodies with
``jax.named_scope`` (``sweep.gather``, ``sweep.sort``, ``sweep.reduce``,
``sweep.wake``; see ``src/repro/core/lpa.py``) and opens a
``jax.profiler.TraceAnnotation`` for each of its stage spans
(``engine.prepare``, ``engine.propagate``, ...).  This module reads both out
of the ``.xplane.pb`` of a traced run:

* every operation on an ``XLA Ops`` line of a device plane inside
  ``bench.window`` is put down to the innermost ``sweep.*`` component of
  its scope path: the ``op_name`` metadata of its instruction in the
  compiled program.  A chip's trace names each operation and, on the
  ``XLA Modules`` line, the program execution around it; the programs
  themselves ride in the ``/host:metadata`` plane as ``Hlo Proto`` stats,
  which ``hlo_op_names`` decodes.  Control-flow operations span the
  operations they run and are left out, as in ``tracing.reduce_trace``;
* the device-idle time inside host annotations of given names is each
  annotation's stretch of the window less the union of the device's
  operation intervals.

The trace is loaded once per run and kept for every reader of that run
(``load``).  A program without the scopes or the spans (one that predates
them) gives no seconds, and the readers then return None.

    cd bench && python3 -m lpabench.scopes .cache/trace/<cell>

prints a traced run's device seconds by scope, for all operations and for
the sweep programs, the share of the latter in no scope, and the top
operations with their scopes.
"""
from __future__ import annotations

import bisect
import json
import os
import sys
from collections import defaultdict
from dataclasses import dataclass, field

from lpabench import tracing

SCOPE_PREFIX = "sweep."
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
# Name prefixes of the program's own spans (repro.obs.trace).
SPAN_PREFIXES = ("engine.", "ooc.", "serve.", "batch.")
SWEEP_PROGRAMS = ("jit__propagate", "jit__split")


@dataclass(frozen=True)
class Op:
    plane: str
    module: str         # the program, e.g. ``jit__propagate_fused(12)``
    name: str           # the HLO op, e.g. ``fusion.9``
    scope: str          # innermost sweep.* component, "" when none
    start_ns: float     # clipped to the window
    end_ns: float


@dataclass
class ScopeTrace:
    window: tuple[float, float]
    ops: list = field(default_factory=list)     # Op, leaf ops in the window
    spans: list = field(default_factory=list)   # tracing.Event, host spans

    @property
    def devices(self) -> int:
        return max(len({o.plane for o in self.ops}), 1)

    def scope_seconds(self, programs: tuple[str, ...] = ()) -> dict:
        """Device seconds per scope ("" for none), per device; only the
        scopes some operation carries appear.  ``programs`` keeps the
        operations of the modules whose names start with one of them."""
        out = defaultdict(float)
        for o in self.ops:
            if not programs or o.module.startswith(programs):
                out[o.scope] += (o.end_ns - o.start_ns) / 1e9
        return {k: v / self.devices for k, v in out.items()}

    def idle_seconds_in(self, names: tuple[str, ...]) -> float | None:
        """Device-idle seconds inside the host spans named ``names``, per
        device; None when no such span lies in the window."""
        inside = tracing._union(
            (s.start_ns, s.end_ns) for s in self.spans if s.name in names)
        if not inside:
            return None
        busy = defaultdict(list)
        for o in self.ops:
            busy[o.plane].append((o.start_ns, o.end_ns))
        span_ns = sum(e - s for s, e in inside)
        idle = []
        for iv in busy.values() or [[]]:
            covered = sum(max(0.0, min(e, b) - max(s, a))
                          for s, e in inside for a, b in tracing._union(iv))
            idle.append(span_ns - covered)
        return sum(idle) / len(idle) / 1e9


def scope_of(path: str) -> str:
    """``jit(f)/while/body/sweep.reduce/jit(g)/sweep.gather/gather``
    -> ``sweep.gather``; "" when no component is a sweep scope."""
    comps = [c for c in path.split("/") if c.startswith(SCOPE_PREFIX)]
    return comps[-1] if comps else ""


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """The ``(field number, value)`` pairs of one protobuf message: varints
    as ints, everything else as a view of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _field(buf, number: int, default=b""):
    return next((v for f, v in _fields(buf) if f == number), default)


def _instruction_op_names(hlo_proto) -> dict:
    """``HloProto`` bytes -> {instruction name: ``op_name`` metadata}."""
    out = {}
    module = _field(hlo_proto, 1)                     # HloProto.hlo_module
    for f, comp in _fields(module):
        if f != 3:                                    # .computations
            continue
        for g, inst in _fields(comp):
            if g != 2:                                # .instructions
                continue
            name = path = b""
            for h, v in _fields(inst):
                if h == 1:                            # .name
                    name = v
                elif h == 7:                          # .metadata
                    path = _field(v, 2)               # OpMetadata.op_name
            out[bytes(name).decode()] = bytes(path).decode()
    return out


def hlo_op_names(xspace: bytes) -> dict:
    """{program name as the trace gives it: {HLO op: ``op_name``}} from the
    ``Hlo Proto`` stats of an XSpace's ``/host:metadata`` plane."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1 or bytes(_field(plane, 2)).decode() != METADATA_PLANE:
            continue
        stat_ids = {_field(v, 1, 0) for g, e in _fields(plane) if g == 5
                    for v in [_field(e, 2)]
                    if bytes(_field(v, 2)).decode() == HLO_PROTO_STAT}
        for g, entry in _fields(plane):
            if g != 4:                                # .event_metadata
                continue
            meta = _field(entry, 2)
            for h, stat in _fields(meta):
                if h == 5 and _field(stat, 1, 0) in stat_ids:
                    out[bytes(_field(meta, 2)).decode()] = \
                        _instruction_op_names(_field(stat, 6))
    return out


def parse(data, op_names: dict, window: str = tracing.WINDOW,
          device_plane: str = tracing.DEVICE_PLANE,
          op_line: str = tracing.OP_LINE,
          module_line: str = tracing.MODULE_LINE,
          host_plane: str = tracing.HOST_PLANE) -> ScopeTrace:
    """A ``ProfileData`` (or anything with its planes, lines and events)
    and the programs' ``op_names`` (``hlo_op_names``) to the operations
    and spans of the window."""
    win = None
    spans = []
    raw = []
    modules = defaultdict(list)
    for plane in data.planes:
        device = plane.name.startswith(device_plane)
        host = plane.name == host_plane
        if not (device or host):
            continue
        for line in plane.lines:
            ops = device and line.name.startswith(op_line)
            for ev in line.events:
                if host and ev.name == window:
                    win = (float(ev.start_ns),
                           float(ev.start_ns) + float(ev.duration_ns))
                elif host and ev.name.startswith(SPAN_PREFIXES):
                    spans.append(tracing.Event(
                        plane.name, line.name, ev.name, float(ev.start_ns),
                        float(ev.duration_ns)))
                elif ops:
                    raw.append((plane.name, ev))
                elif device and line.name == module_line:
                    modules[plane.name].append(
                        (float(ev.start_ns),
                         float(ev.start_ns) + float(ev.duration_ns),
                         ev.name))
    if win is None:
        raise ValueError(f"no {window!r} span in the trace")
    w0, w1 = win
    for mods in modules.values():
        mods.sort()
    starts = {p: [m[0] for m in mods] for p, mods in modules.items()}
    out = ScopeTrace(window=win)
    for plane, ev in raw:
        s, e = float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns)
        if e <= w0 or s >= w1:
            continue
        name = tracing.short_op(ev.name)
        if name.split(".")[0] in tracing.CONTROL_FLOW:
            continue
        module = ""
        i = bisect.bisect_right(starts.get(plane, []), s) - 1
        if i >= 0 and modules[plane][i][1] >= s:
            module = modules[plane][i][2]
        path = op_names.get(module, {}).get(name, "")
        out.ops.append(Op(plane, module, name, scope_of(path),
                          max(s, w0), min(e, w1)))
    out.spans = [sp for sp in spans if sp.end_ns > w0 and sp.start_ns < w1]
    return out


_LOADED: dict = {}


def read_xplane(path: str) -> ScopeTrace:
    from jax.profiler import ProfileData
    with open(path, "rb") as fh:
        xspace = fh.read()
    return parse(ProfileData.from_serialized_xspace(xspace),
                 hlo_op_names(xspace))


def load(run) -> ScopeTrace:
    """The run's trace, read once and kept for the other readers."""
    from lpabench.harness import TRACE_DIR
    path = tracing.newest_xplane(str(TRACE_DIR / run.cell.name))
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = read_xplane(path)
    return _LOADED[key]


def scope_ms_per_fit(run, win, summary, scope: str) -> float | None:
    """Device ms per fit of the window's operations in ``scope``; None
    without a trace, without fits, or when no operation carries it."""
    if summary is None or not win.records:
        return None
    seconds = load(run).scope_seconds()
    if scope not in seconds:
        return None
    return 1e3 * seconds[scope] / len(win.records)


def idle_ms_per_fit(run, win, summary, names) -> float | None:
    """Device-idle ms per fit inside the host spans ``names``."""
    if summary is None or not win.records:
        return None
    seconds = load(run).idle_seconds_in(tuple(names))
    return None if seconds is None else 1e3 * seconds / len(win.records)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    t = read_xplane(tracing.newest_xplane(argv[0]))
    sweeps = t.scope_seconds(SWEEP_PROGRAMS)
    top = defaultdict(float)
    for o in t.ops:
        top[(o.module, o.name, o.scope)] += (o.end_ns - o.start_ns) / 1e9
    print(json.dumps({
        "all_ops_s": t.scope_seconds(), "sweep_programs_s": sweeps,
        "sweep_unscoped_share": sweeps.get("", 0.0) / sum(sweeps.values())
        if sweeps else None,
        "top_ops": [[f"{m}:{n}", sc, v / t.devices] for (m, n, sc), v in
                    sorted(top.items(), key=lambda kv: -kv[1])[:12]]},
        indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
