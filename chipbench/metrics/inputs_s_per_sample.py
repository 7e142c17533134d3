"""Compile stage: seconds of the program's ``inputs`` stage inside the
window (summed over chips), per sample: a job's images made and copied to
its chip, inside the job's first ``compile`` stage."""

from chipbench import spans


def read(run):
    if not run.samples:
        return None
    return spans.stage_seconds(run.events, "inputs", run.start, run.deadline) / len(run.samples)
