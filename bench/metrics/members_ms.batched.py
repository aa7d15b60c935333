"""Host ms per dispatch after the device work: the ``engine.members`` span
of ``Engine.fit_many`` (each member's rows unpacked, compacted and made a
``DetectionResult``), averaged over the dispatches of the window (program
span).  A program without the span gives no reading."""
from lpabench import spec


def read(run, win, summary):
    return spec.traffic_module(run.cell).span_ms(win, "engine.members")
