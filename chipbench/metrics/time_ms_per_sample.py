"""Timing stage: milliseconds of the program's ``time`` stage inside the
window (summed over chips), per sample."""

from chipbench import spans


def read(run):
    if not run.samples:
        return None
    return spans.stage_seconds(run.events, "time", run.start, run.deadline) * 1e3 / len(run.samples)
