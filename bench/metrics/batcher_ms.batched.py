"""The batcher's own ms per dispatch: its ``batch.collect`` span (from the
first request taken to dispatch, the linger included) plus its
``batch.settle`` span (results handed to the waiting requests), each
averaged over the dispatches of the window (program span).  A program
without either span gives no reading."""
from lpabench import spec


def read(run, win, summary):
    return spec.traffic_module(run.cell).span_ms(win, "batch.collect",
                                                 "batch.settle")
