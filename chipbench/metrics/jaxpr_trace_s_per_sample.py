"""Compile stage: seconds of JAX's jaxpr tracing inside the window, per
sample: the program's ``compile.trace`` events (JAX's own phase durations,
forwarded to the writer whose stage was open).  Traces nest, so each
writer's events count by their union; summed over chips."""

from chipbench import spans


def read(run):
    if not run.samples:
        return None
    return spans.stage_seconds(run.events, "compile.trace", run.start,
                               run.deadline) / len(run.samples)
