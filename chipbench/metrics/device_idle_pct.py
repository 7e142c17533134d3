"""Device: the share of the window in which no operation ran, from the
profiler trace, averaged over the cell's chips."""


def read(run):
    if not run.window_s:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
