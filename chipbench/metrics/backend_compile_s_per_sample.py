"""Compile stage: seconds of the XLA/Mosaic backend compile inside the
window, per sample: the program's ``compile.backend`` events, by their
union per writer, summed over chips."""

from chipbench import spans


def read(run):
    if not run.samples:
        return None
    return spans.stage_seconds(run.events, "compile.backend", run.start,
                               run.deadline) / len(run.samples)
