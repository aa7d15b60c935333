"""Propagation time per fit, from ``DetectionResult.timings``: the host
clock around the backend's propagate program, up to its result."""


def read(run, win, summary):
    fits = [r for r in win.records if r is not None]
    if not fits:
        return None
    return 1e3 * sum(r.timings["propagation"] for r in fits) / len(fits)
