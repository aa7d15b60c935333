"""Host time per fit in the engine's front: ``prepare`` (padding, tile
building, transfers) plus ``compact``, from ``DetectionResult.timings``."""


def read(run, win, summary):
    fits = [r for r in win.records if r is not None]
    if not fits:
        return None
    return 1e3 * sum(r.timings["prepare"] + r.timings["compact"]
                     for r in fits) / len(fits)
