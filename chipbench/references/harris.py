"""ImageCL harris (Tørring & Elster, arXiv 2203.13577, section V.D): 3x3
Sobel gradients, structure-tensor products, 3x3 box sums and the response
det(M) - k trace(M)^2, on an image zero-extended by the stencil radius 2.
Copied from the program's ``kernels/harris/ref.py``: convolutions by
``lax.conv_general_dilated``, so it shares no code with the kernel's
shift-and-add form.  On a TPU, XLA lays these one-channel convolutions out
in more memory than the chip has at 8192², so the configuration runs it on
the host's CPU."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

HARRIS_K = 0.04

SOBEL_X = ((-1.0, 0.0, 1.0), (-2.0, 0.0, 2.0), (-1.0, 0.0, 1.0))
BOX = ((1.0, 1.0, 1.0), (1.0, 1.0, 1.0), (1.0, 1.0, 1.0))


def _conv3_valid(img, kern):
    out = lax.conv_general_dilated(
        img[None, None],
        jnp.asarray(kern, img.dtype)[None, None],
        window_strides=(1, 1),
        padding="VALID",
        # full precision of the operands' type on every backend
        precision=lax.Precision.HIGHEST,
    )
    return out[0, 0]


def reference(img, k: float = HARRIS_K):
    sobel_y = tuple(zip(*SOBEL_X, strict=True))
    padded = jnp.pad(img, 2)
    ix = _conv3_valid(padded, SOBEL_X)
    iy = _conv3_valid(padded, sobel_y)
    sxx = _conv3_valid(ix * ix, BOX)
    syy = _conv3_valid(iy * iy, BOX)
    sxy = _conv3_valid(ix * iy, BOX)
    det = sxx * syy - sxy * sxy
    trace = sxx + syy
    return det - jnp.asarray(k, img.dtype) * trace * trace


def bytes_moved(x: int, y: int) -> int:
    """One f32 image read, one written (the 2-row halos re-read per band
    are the kernel's choice, not the problem's)."""
    return 2 * x * y * 4
