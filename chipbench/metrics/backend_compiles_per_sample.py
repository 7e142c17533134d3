"""Compile stage: backend compiles per sample: the program's
``compile.backend`` events that ended inside the window, whatever stage
they came in (``within``: a recompile inside the timing stage counts)."""

from chipbench import spans


def read(run):
    if not run.samples:
        return None
    return spans.stage_count(run.events, "compile.backend", run.start,
                             run.deadline) / len(run.samples)
