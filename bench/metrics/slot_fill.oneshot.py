"""Share of the edge slots the sweeps run over that hold an edge: 100 x the
directed edges of the window's fits over their ``edge_slots`` (the padded
edge bucket on ``segment``, the padded tiles' rows x d on ``tile``), which
``DetectionResult`` carries.  A program that does not report it gives no
reading."""


def read(run, win, summary):
    slots = [getattr(r, "edge_slots", None) for r in win.records]
    if not slots or None in slots or sum(slots) == 0:
        return None
    return 100.0 * sum(win.info["fit_edges"]) / sum(slots)
