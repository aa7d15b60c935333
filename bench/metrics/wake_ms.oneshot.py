"""Device ms per fit of the operations in the ``sweep.wake`` scope, from
the trace (lpabench/scopes.py).  No such operation, no reading."""
from lpabench import scopes


def read(run, win, summary):
    return scopes.scope_ms_per_fit(run, win, summary, "sweep.wake")
