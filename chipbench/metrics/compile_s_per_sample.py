"""Compile stage: seconds of the program's ``compile`` stage inside the
window (summed over chips), per sample."""

from chipbench import spans


def read(run):
    if not run.samples:
        return None
    return spans.stage_seconds(run.events, "compile", run.start, run.deadline) / len(run.samples)
