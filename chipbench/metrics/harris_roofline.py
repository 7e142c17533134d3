"""Kernel: harris's share of its roofline, read as ``best_kernel_roofline``
reads add's: the problem's bytes (one f32 image read, one written, from
``references/harris.py``, whatever config runs) at the chip's HBM peak,
over the best config's device time per call under the ``retime`` mark.
That time is the whole jitted program, so the row pad and crop of a band
that does not divide the image count against it."""

from chipbench.metrics.best_kernel_roofline import read

__all__ = ["read"]
