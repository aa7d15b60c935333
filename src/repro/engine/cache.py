"""Shape-bucketed compile cache + trace-count instrumentation.

The cache maps (backend, bucket, algorithm statics, placement statics) to
a prepared *plan* — the backend's jitted executables specialised to the
bucket shapes.  A traffic stream of same-bucket graphs pays tracing and
XLA compilation exactly once.

``TRACE_LOG`` is the observability hook the acceptance tests assert on:
backends call ``TRACE_LOG.record(tag)`` inside their traced function
bodies, which Python only executes on an actual (re)trace — cache hits,
both in this cache and in jax's own jit cache, leave the counters
untouched.
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
from collections import Counter
from typing import Any, Callable, Hashable

# Workload attribution for the trace auditor: the engine (and the ooc
# driver) set the current (backend, bucket) around each backend dispatch,
# so a TRACE_LOG.record fired from inside a traced body lands in the
# right per-workload-context bin.  A ContextVar keeps nested/threaded
# engines from clobbering each other.
_TRACE_CONTEXT: contextvars.ContextVar[tuple | None] = \
    contextvars.ContextVar("repro_trace_context", default=None)


def current_trace_context() -> tuple | None:
    return _TRACE_CONTEXT.get()


@contextlib.contextmanager
def trace_context(backend: str, bucket):
    """Attribute any traces fired in the body to ``(backend, bucket)``."""
    token = _TRACE_CONTEXT.set((backend, tuple(bucket)
                                if isinstance(bucket, (list, tuple))
                                else bucket))
    try:
        yield
    finally:
        _TRACE_CONTEXT.reset(token)


class TraceLog:
    """Counts jit traces per backend stage (e.g. ``"segment:propagate"``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counts: Counter[str] = Counter()
        # (tag, trace-context) -> count; context None for unattributed
        self.context_counts: Counter[tuple] = Counter()

    def record(self, tag: str) -> None:
        ctx = _TRACE_CONTEXT.get()
        with self._lock:
            self.counts[tag] += 1
            self.context_counts[(tag, ctx)] += 1

    def total(self, prefix: str = "") -> int:
        with self._lock:
            return sum(v for k, v in self.counts.items()
                       if k.startswith(prefix))

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self.counts)

    def context_snapshot(self) -> dict[tuple, int]:
        with self._lock:
            return dict(self.context_counts)

    def reset(self) -> None:
        with self._lock:
            self.counts.clear()
            self.context_counts.clear()


TRACE_LOG = TraceLog()


class CompileCache:
    """Keyed store of backend plans with hit/miss accounting."""

    def __init__(self):
        self._lock = threading.Lock()
        self._plans: dict[Hashable, Any] = {}
        self.hits = 0
        self.misses = 0

    def get_or_build(self, key: Hashable,
                     builder: Callable[[], Any]) -> tuple[Any, bool]:
        """Returns (plan, was_hit).  Builders run outside the lock is not
        needed here — plan building is cheap (tracing happens lazily on
        the first call of each jitted function)."""
        with self._lock:
            if key in self._plans:
                self.hits += 1
                return self._plans[key], True
            self.misses += 1
        plan = builder()
        with self._lock:
            self._plans.setdefault(key, plan)
            return self._plans[key], False

    def __len__(self) -> int:
        return len(self._plans)

    def plans(self) -> dict[Hashable, Any]:
        """A snapshot of the cached plans by key."""
        with self._lock:
            return dict(self._plans)

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self.hits = 0
            self.misses = 0

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {"plans": len(self._plans), "hits": self.hits,
                    "misses": self.misses}


# Default process-wide cache: every Engine without an explicit cache shares
# it, so e.g. the `gsl_lpa` wrapper and a user's Engine reuse executables.
GLOBAL_CACHE = CompileCache()
