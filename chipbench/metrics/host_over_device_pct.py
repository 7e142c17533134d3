"""Timing stage: the value the tuner recorded for the window's best config
(the median of its host-timed, singly fenced calls) as a share of that
program's device time per call in the trace of the re-timing."""


def read(run):
    if run.best is None or not run.best_device_s:
        return None
    return 100.0 * run.best.value / run.best_device_s
