"""Split-Last time per fit, from ``DetectionResult.timings``."""


def read(run, win, summary):
    fits = [r for r in win.records if r is not None]
    if not fits:
        return None
    return 1e3 * sum(r.timings["split"] for r in fits) / len(fits)
