"""Share of the tile cells the batched sweeps run over that hold an edge:
100 x the directed edges of the window's requests over the ``edge_slots``
of their dispatches (program counter).  Every member of a dispatch
carries that dispatch's ``edge_slots``, so each dispatch counts it once
(dispatches as the batched traffic groups its records).  A program that
does not report it gives no reading."""
from lpabench import spec


def read(run, win, summary):
    groups = spec.traffic_module(run.cell).dispatches(win.records)
    slots = sum(getattr(g[0].result, "edge_slots", None) or 0
                for g in groups)
    edges = sum(m.edges for g in groups for m in g)
    return 100.0 * edges / slots if slots else None
