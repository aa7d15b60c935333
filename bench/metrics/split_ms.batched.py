"""Split-Last ms per dispatch: the ``engine.split`` span around the batched
split program, averaged over the dispatches of the window (program
span)."""
from lpabench import spec


def read(run, win, summary):
    return spec.traffic_module(run.cell).span_ms(win, "engine.split")
