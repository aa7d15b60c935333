"""R006 — telemetry discipline in hot-path sweep code.

The observability layer (``repro.obs``) is host-side bookkeeping by
contract: spans and registry writes wrap *stage boundaries* (engine
prepare/propagate/split/compact, ooc phases, serving admission→settle),
never the per-sweep inner loops, and convergence profiles record
device-side into preallocated buffers precisely so no telemetry runs per
sweep.  The in-program device scope is ``jax.named_scope`` (the
``sweep.*`` scopes of the sweep bodies): it only names operations in the
compiled program's metadata, costs nothing at run time, and is allowed
inside traced code.
This rule enforces that contract inside the hot modules (``core/``,
``kernels/``, ``engine/backends/``):

* **traced scopes** (functions handed to ``jax.jit`` / ``shard_map`` /
  ``lax.while_loop``): any host timer (``time.perf_counter`` & friends),
  tracer span, or metrics-registry call — under trace these either fail
  or burn a host call into every sweep of the compiled loop;
* **sweep-dispatch loops**: the same calls inside a ``for``/``while``
  body that dispatches jitted sweep callables (``plan.step(...)``,
  ``sweeps.move(...)``) — a timer or counter per sweep reintroduces
  exactly the per-iteration host overhead the fused dispatch work
  removed.  Stage-boundary timing *around* such loops stays legal.

Deliberate exceptions carry ``# lint: telemetry-ok — <why>``.
"""
from __future__ import annotations

import ast

from repro.analysis.findings import Finding
from repro.analysis.rules.base import ModuleContext, Rule, dotted_name
from repro.analysis.rules.r001_host_sync import (
    _all_functions,
    _PLAN_RECEIVERS,
    _SWEEP_METHODS,
    _traced_functions,
)

_HOT_PREFIXES = ("core/", "kernels/", "engine/backends/")

# Host wall-clock reads (bare names cover `from time import perf_counter`).
_TIMER_CALLS = {"time.perf_counter", "perf_counter", "time.monotonic",
                "monotonic", "time.perf_counter_ns", "time.time"}
# Span tracer entry points (repro.obs.trace).
_SPAN_CALLS = {"span", "TRACER.span", "tracer.span"}
# Metric-handle mutators (repro.obs.registry Counter/Gauge/Histogram).
# ``.set`` is deliberately absent: ``buf.at[row].set(...)`` is the jax
# in-place update idiom all over the hot modules.
_METRIC_METHODS = {"inc", "observe"}
# Registry roots: REGISTRY.counter(...), scope.histogram(...), etc.
_REGISTRY_ROOTS = {"REGISTRY", "registry"}
_REGISTRY_METHODS = {"counter", "gauge", "histogram", "scope"}
# Quality hooks (repro.obs.quality + DetectionResult.check_connected):
# host-side reductions over the *final* labels by contract — inside a
# traced function they burn a trace-time device pass into the
# executable; inside a sweep loop they pay a full modularity /
# connectivity pass per sweep.  They run once, post-convergence, at the
# stage boundary the engine already owns.
_QUALITY_CALLS = {"compute_quality", "record_report", "label_churn",
                  "check_connected"}


def _telemetry_call(node: ast.Call) -> str | None:
    """Short description when ``node`` is a telemetry call, else None."""
    name = dotted_name(node.func)
    if name in _TIMER_CALLS:
        return f"host timer {name}()"
    if name in _SPAN_CALLS:
        return f"tracer span {name}()"
    if name in _QUALITY_CALLS:
        return f"quality hook {name}()"
    if isinstance(node.func, ast.Attribute):
        attr = node.func.attr
        if attr in _QUALITY_CALLS:
            return f"quality hook .{attr}()"
        if attr in _METRIC_METHODS:
            return f"metric write .{attr}()"
        root = dotted_name(node.func.value)
        if root in _REGISTRY_ROOTS and attr in _REGISTRY_METHODS:
            return f"registry call {root}.{attr}()"
    return None


def _is_sweep_dispatch(node: ast.Call) -> bool:
    func = node.func
    return (isinstance(func, ast.Attribute) and func.attr in _SWEEP_METHODS
            and isinstance(func.value, ast.Name)
            and func.value.id in _PLAN_RECEIVERS)


class TelemetryRule(Rule):
    id = "R006"
    tag = "telemetry"
    description = ("telemetry (perf_counter / spans / metric writes) inside "
                   "jitted or per-sweep hot-path code")

    def applies(self, relpath: str) -> bool:
        return relpath.startswith(_HOT_PREFIXES)

    def check(self, ctx: ModuleContext) -> list[Finding]:
        findings: list[Finding] = []
        traced = _traced_functions(ctx.tree)
        for fn in _all_functions(ctx.tree):
            if fn in traced:
                findings.extend(self._check_traced(ctx, fn))
            else:
                findings.extend(self._check_sweep_loops(ctx, fn))
        return findings

    def _check_traced(self, ctx: ModuleContext,
                      fn: ast.FunctionDef) -> list[Finding]:
        out = []
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            what = _telemetry_call(node)
            if what:
                out.append(self.finding(
                    ctx, node,
                    f"{what} inside jit-traced '{fn.name}' — telemetry "
                    f"must stay host-side at stage boundaries (use the "
                    f"device-side profile buffer for per-sweep counts)"))
        return out

    def _check_sweep_loops(self, ctx: ModuleContext,
                           fn: ast.FunctionDef) -> list[Finding]:
        out = []
        for loop in (n for n in ast.walk(fn)
                     if isinstance(n, (ast.For, ast.While))):
            if not any(_is_sweep_dispatch(c) for c in ast.walk(loop)
                       if isinstance(c, ast.Call)):
                continue
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                what = _telemetry_call(node)
                if what:
                    out.append(self.finding(
                        ctx, node,
                        f"{what} inside a sweep-dispatch loop in "
                        f"'{fn.name}' — per-sweep telemetry reintroduces "
                        f"per-iteration host overhead; time the loop as "
                        f"one stage instead"))
        # nested loops walk the same nodes twice: one finding per site
        seen: set[tuple[int, int]] = set()
        uniq = []
        for f in out:
            if (f.line, f.col) not in seen:
                seen.add((f.line, f.col))
                uniq.append(f)
        return uniq
