"""Device-idle ms per fit inside the engine's front: the stretches of the
``engine.prepare`` and ``engine.compact`` host annotations in which no
operation ran on the device, from the trace (lpabench/scopes.py).  A fit
is synchronous, so this reads about ``host_ms.oneshot``; the two part once
front work overlaps device work.  No such annotation, no reading."""
from lpabench import scopes

SPANS = ("engine.prepare", "engine.compact")


def read(run, win, summary):
    return scopes.idle_ms_per_fit(run, win, summary, SPANS)
