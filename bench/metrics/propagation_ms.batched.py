"""Propagation ms per dispatch: the ``engine.propagate`` span around the
batched propagate program, up to its result, averaged over the
dispatches of the window (program span)."""
from lpabench import spec


def read(run, win, summary):
    return spec.traffic_module(run.cell).span_ms(win, "engine.propagate")
