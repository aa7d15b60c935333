"""The sweeps' share of the HBM roofline, by the sweep byte model.

Bytes: ``(12 m + 8 n)`` per propagation or split iteration of each fit in
the window, with each fit's own graph's ``m`` and ``n``
(lpabench/bytemodel.py).  Time: the device time of the propagation and
split programs (``jit__propagate*``, ``jit__split*``) inside the window,
from the trace.  The model counts the work, not the layout, so it reads
the same whichever backend or kernel runs the sweep.  No such program in
the trace, no reading.
"""
from lpabench import bytemodel, tracing

PROGRAMS = ("jit__propagate", "jit__split")


def read(run, win, summary):
    if summary is None:
        return None
    seconds = tracing.module_seconds(summary, PROGRAMS)
    nbytes = sum(bytemodel.sweep_bytes(m, n, r.lpa_iterations
                                       + r.split_iterations)
                 for m, n, r in zip(win.info["fit_edges"], win.info["fit_n"],
                                    win.records))
    return bytemodel.roofline_share(nbytes, 0, seconds, run.peaks)
