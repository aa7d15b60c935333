"""Share of the traced window in which no operation ran on the device."""


def read(run, win, summary):
    return None if summary is None else 100.0 * summary.idle_share
