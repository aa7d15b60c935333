"""Share of the member-sweeps of the window's dispatches spent on members
that had already converged (program counter).  A dispatch's loops run
until its slowest member is done, so each of its ``k`` members rides
``max lpa + max split`` iterations and needs its own ``lpa_iterations +
split_iterations``:

    100 x (1 - sum of members' (lpa + split)
                / sum over dispatches of k x (max lpa + max split))

Dispatches as the batched traffic groups its records."""
from lpabench import spec


def read(run, win, summary):
    needed = ridden = 0
    for group in spec.traffic_module(run.cell).dispatches(win.records):
        its = [(m.result.lpa_iterations, m.result.split_iterations)
               for m in group]
        needed += sum(a + b for a, b in its)
        ridden += len(its) * (max(a for a, _ in its) + max(b for _, b in its))
    return 100.0 * (1.0 - needed / ridden) if ridden else None
