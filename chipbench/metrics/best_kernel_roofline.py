"""Kernel: the share of its roofline that the window's best config reaches:
the least time of one call, the problem's bytes (``bytes_moved`` of the
configuration's reference, from the problem's shapes, whatever config
runs) at the chip's published HBM bandwidth (``peaks.py``), over its device
time per call in the trace of the re-timing.  For kernels bound by memory,
as add and harris are."""

from chipbench import peaks


def read(run):
    if run.best is None:
        return None
    return peaks.roofline_pct(run.bytes_moved, run.peak, run.best_device_s)
