"""ImageCL add (Tørring & Elster, arXiv 2203.13577, section V.D): two
images summed element by element.  Copied from the program's
``kernels/add/ref.py``."""

from __future__ import annotations


def reference(a, b):
    return a + b


def bytes_moved(x: int, y: int) -> int:
    """Two f32 images read, one written."""
    return 3 * x * y * 4
