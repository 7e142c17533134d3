"""Executor: how unevenly the chips shared the window's samples, as
(max - min) / mean of the samples each telemetry writer (one per chip)
recorded.  Needs at least two writers."""


def read(run):
    if len(run.workers) < 2:
        return None
    counts = [sum(s.src == w for s in run.samples) for w in run.workers]
    mean = sum(counts) / len(counts)
    return 100.0 * (max(counts) - min(counts)) / mean if mean > 0 else None
