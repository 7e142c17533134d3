"""Compile stage: compilations per sample.  Each ``compile`` stage event is
one increment of the program's ``compiles`` counter when no CompileCache
is attached (the window runs without one), and unlike the counter it
carries the time it ended, so only the window's are counted."""

from chipbench import spans


def read(run):
    if not run.samples:
        return None
    return spans.stage_count(run.events, "compile", run.start, run.deadline) / len(run.samples)
