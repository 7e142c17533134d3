"""Searcher and session: host time per sample inside tuning jobs that no
pipeline stage accounts for (ask/tell, stores, session wiring), from the
program's ``experiment`` spans less its ``stage`` events, per writer."""

from chipbench import spans


def read(run):
    if not run.samples:
        return None
    s = spans.uncovered_seconds(run.events, "experiment", run.start, run.deadline)
    return s * 1e3 / len(run.samples)
