"""Compile stage: seconds of JAX's lowering of jaxprs to MLIR modules
(Pallas's lowering to Mosaic among them) inside the window, per sample: the
program's ``compile.lower`` events, by their union per writer, summed over
chips."""

from chipbench import spans


def read(run):
    if not run.samples:
        return None
    return spans.stage_seconds(run.events, "compile.lower", run.start,
                               run.deadline) / len(run.samples)
