"""One run of one cell: set-up, the measured window, the checks, the line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Order of a run:

1. read the cell's files; on a one-chip cell hold one chip before JAX
   starts; refuse to go on without a TPU or with fewer chips than the cell
   asks for (exit 3, no result line);
2. set-up, timed from the process's start as ``setup_s``: JAX's persistent
   compile cache in the checkout, the traffic module's inputs from the
   seed and its warm-up of every shape the window will use;
3. the window, ``--seconds`` long, under the host annotation
   ``bench.window`` and, with ``--trace 1``, under the profiler; compiles
   inside it are counted and printed with the cell's effective ``params``
   and the traffic module's own information (``{"info": "window"}``);
4. the device's peak memory is read, the program's state is dropped, and
   the traffic module compares what the window produced with the plain reference;
5. the last line of standard output is one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
   traced, and ``checks`` last; the checks also go to standard error.

A traffic module (``traffic/<kind>.py``) provides ``setup(run)``,
``window(run, state)`` and ``check(run, state, window)``.  A per-layer
metric's reader (``metrics/<name>.py``) provides ``read(run, window,
summary)`` and returns a number, or None when it finds nothing to read.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from lpabench import spec, tracing

SRC = spec.ROOT / "src"
TRACE_DIR = spec.BENCH_DIR / ".cache" / "trace"
EXIT_NO_DEVICE = 3


class NoDevice(RuntimeError):
    pass


@dataclass
class Check:
    """One number compared with its limit; passes when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Window:
    """What a traffic module's window returns."""
    end_to_end: dict                      # metric name -> value
    attempted: int
    records: list = field(default_factory=list)
    info: dict = field(default_factory=dict)


@dataclass
class Run:
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float
    peaks: dict | None = None

    @contextlib.contextmanager
    def span(self, name: str):
        """A host annotation that shows in the profiler's trace."""
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield

    def generator(self, family: str):
        return spec.generator(self.cell.bench_dir, family)


class CompileCounter:
    """Compiles, cache loads and traces that jax.monitoring reports."""

    def __init__(self):
        self.counts = {"backend_compiles": 0, "cache_hits": 0, "traces": 0}

    def on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["backend_compiles"] += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.counts["traces"] += 1

    def on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    def install(self) -> None:
        import jax
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)

    def snapshot(self) -> dict:
        return dict(self.counts)


def pin_chips(chips: int) -> None:
    """Hold one chip of a multi-chip host; must run before JAX starts."""
    if chips == 1:
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


def check_device(chips: int, allow_cpu: bool) -> dict:
    import jax
    platform = jax.default_backend()
    if platform != "tpu" and not allow_cpu:
        raise NoDevice(f"no TPU found (JAX backend {platform!r}); this "
                       f"benchmark measures the chip only")
    if jax.device_count() < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees "
                       f"{jax.device_count()}")
    dev = jax.devices()[0]
    # "kind" is the result line's key; "device_kind" is JAX's own name
    return {"platform": dev.platform, "kind": dev.device_kind,
            "device_kind": dev.device_kind, "count": jax.device_count()}


def configure_jax() -> None:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), keeping every program however quick to
    compile, so that a second run of a cell compiles nothing."""
    import jax

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def _emit_info(tag: str, payload: dict) -> None:
    print(json.dumps({"info": tag, **payload}), flush=True)


def traced_window(run: Run, traffic, state):
    """The traffic module's window under ``bench.window``; with ``--trace 1`` also
    under the profiler, whose trace is reduced once the window closes."""
    import jax
    if not run.trace:
        with run.span(tracing.WINDOW):
            return traffic.window(run, state), None
    shutil.rmtree(TRACE_DIR / run.cell.name, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR / run.cell.name))
    try:
        with run.span(tracing.WINDOW):
            win = traffic.window(run, state)
    finally:
        jax.profiler.stop_trace()
    events = tracing.load_events(tracing.newest_xplane(
        str(TRACE_DIR / run.cell.name)))
    return win, tracing.reduce_trace(events)


def per_layer(run: Run, win: Window, summary) -> dict:
    out = {}
    for m in run.cell.per_layer:
        value = spec.metric_reader(run.cell.bench_dir, m["name"]).read(
            run, win, summary)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end(run: Run, win: Window, setup_s: float) -> dict:
    values = {"setup_s": setup_s, **win.end_to_end}
    out = {}
    for m in run.cell.end_to_end:
        if m["name"] not in values:
            raise KeyError(f"the {run.cell.traffic['kind']} traffic reports "
                           f"no {m['name']!r}")
        out[m["name"]] = {"value": float(values[m["name"]]),
                          "unit": m["unit"]}
    return out


def execute(cell: spec.Cell, seed: int, seconds: float, trace: bool,
            t_process: float, allow_cpu: bool = False) -> dict:
    """Run one cell; returns the result object (the last line's JSON)."""
    pin_chips(cell.chips)
    device = check_device(cell.chips, allow_cpu)
    sys.path.insert(0, str(SRC))
    from lpabench.peaks import peaks_for
    configure_jax()
    counter = CompileCounter()
    counter.install()

    run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
              t_process=t_process,
              peaks=peaks_for(device["device_kind"]) if not allow_cpu else
              peaks_for("TPU v5 lite"))
    traffic = spec.traffic_module(cell)
    state = traffic.setup(run)
    before = counter.snapshot()
    setup_s = time.perf_counter() - t_process
    win, summary = traced_window(run, traffic, state)
    after = counter.snapshot()
    in_window = {k: after[k] - before[k] for k in after}
    _emit_info("window", {"compiles_in_window": in_window,
                          "params": cell.config["params"], **win.info})

    device["memory_peak_bytes"] = memory_peak(cell.chips)
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    checks, failed = traffic.check(run, state, win)
    del state

    if trace:
        metrics = per_layer(run, win, summary)
    else:
        metrics = end_to_end(run, win, setup_s)
    result = {"correct": failed == 0 and all(c.ok for c in checks),
              "attempted": win.attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def report(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {str(result['correct']).lower()}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_process: float | None = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    try:
        result = execute(cell, args.seed, args.seconds, bool(args.trace),
                         t_process)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    report(result)
    return 0
