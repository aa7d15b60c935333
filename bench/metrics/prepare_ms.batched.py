"""Host ms per dispatch in the engine's front: the ``engine.prepare`` span
of ``Engine.fit_many`` (pack the members, bucket, build and upload the
tiles), averaged over the dispatches of the window (program span)."""
from lpabench import spec


def read(run, win, summary):
    return spec.traffic_module(run.cell).span_ms(win, "engine.prepare")
