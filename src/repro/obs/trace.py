"""Span tracer: contextvar-nested wall-time spans + Chrome-trace export.

The same attribution idea as ``trace_context`` in ``engine/cache.py`` —
a ContextVar carries the current span so nested stages parent correctly
across threads and concurrent engines — but recording *durations*
instead of retrace counts.  Spans wrap host-side stage boundaries only
(engine prepare/propagate/split/compact, ooc partition visits / prefetch
/ halo exchange, serving admission→dispatch→settle); they never enter
jitted or per-sweep code, which the R006 lint rule enforces.  Inside
traced code the device-side scope is ``jax.named_scope`` (the
``sweep.*`` scopes of the sweep bodies), which names operations in the
compiled program at no run-time cost.

A span always measures its duration (``Span.dur``, the one host clock
for a stage: the engine's ``DetectionResult.timings`` are span
durations).  Only recording depends on ``Tracer.enabled``: an enabled
span is kept in the bounded history and also opens a
``jax.profiler.TraceAnnotation`` of the same bare name, so every stage
shows on the host line of a ``jax.profiler`` trace, on the same clock as
the device operations it launched.

Export is the Chrome trace-event JSON array (``chrome://tracing`` /
Perfetto): complete events (``"ph": "X"``) with microsecond timestamps
relative to tracer start, ``tid`` = OS thread ident so concurrent
request lanes render as parallel tracks.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any

from jax.profiler import TraceAnnotation

_MAX_SPANS = 65536  # bounded history: long servers drop oldest spans

_CURRENT: contextvars.ContextVar["Span | None"] = \
    contextvars.ContextVar("repro_current_span", default=None)

_ids = itertools.count(1)


@dataclasses.dataclass
class Span:
    """One finished (or in-flight) wall-time interval."""
    name: str
    t0: float                      # perf_counter at enter
    dur: float = 0.0               # seconds; 0.0 while in flight
    span_id: int = 0
    parent_id: int = 0             # 0 = root
    tid: int = 0                   # OS thread ident
    attrs: dict[str, Any] = dataclasses.field(default_factory=dict)

    def set(self, **attrs) -> "Span":
        """Attach attributes after enter (counts known only at exit)."""
        self.attrs.update(attrs)
        return self


class Tracer:
    """Bounded in-memory span recorder with a Chrome-trace exporter."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._spans: deque[Span] = deque(maxlen=_MAX_SPANS)
        self._epoch = time.perf_counter()

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time the block; when enabled, also record it and annotate the
        profiler trace.  ``Span.dur`` is set on exit either way."""
        if not self.enabled:
            s = Span(name=name, t0=time.perf_counter(), attrs=attrs)
            try:
                yield s
            finally:
                s.dur = time.perf_counter() - s.t0
            return
        parent = _CURRENT.get()
        s = Span(name=name, t0=time.perf_counter(), span_id=next(_ids),
                 parent_id=parent.span_id if parent else 0,
                 tid=threading.get_ident(), attrs=dict(attrs))
        token = _CURRENT.set(s)
        try:
            with TraceAnnotation(name):
                yield s
        finally:
            _CURRENT.reset(token)
            s.dur = time.perf_counter() - s.t0
            with self._lock:
                self._spans.append(s)

    def current(self) -> Span | None:
        return _CURRENT.get()

    def spans(self, prefix: str = "") -> list[Span]:
        with self._lock:
            return [s for s in self._spans if s.name.startswith(prefix)]

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
        self._epoch = time.perf_counter()

    def chrome_trace(self) -> list[dict]:
        """Trace-event list: complete (``ph:"X"``) events, µs timebase."""
        pid = os.getpid()
        with self._lock:
            spans = list(self._spans)
        events = []
        for s in spans:
            args = {k: v for k, v in s.attrs.items()}
            if s.parent_id:
                args["parent_span"] = s.parent_id
            events.append({
                "name": s.name, "ph": "X", "pid": pid, "tid": s.tid,
                "ts": round((s.t0 - self._epoch) * 1e6, 3),
                "dur": round(s.dur * 1e6, 3),
                "args": args,
            })
        return events

    def export_chrome(self, path) -> int:
        """Write the Chrome-trace JSON array; returns the event count."""
        events = self.chrome_trace()
        with open(path, "w") as fh:
            json.dump(events, fh)
        return len(events)


# Process-global tracer.  ``span("engine.fit")`` is the one-liner every
# stage boundary uses; disable with ``TRACER.enabled = False`` (spans
# then only time the block: no history, no profiler annotation).
TRACER = Tracer()
span = TRACER.span
