"""Pure-jnp oracle for Harris corner detection (paper section V.D).

Pipeline: 3x3 Sobel gradients -> structure-tensor products -> 3x3 box
filter -> Harris response R = det(M) - k * trace(M)^2.  Boundary semantics:
the image is zero-extended by the total stencil radius (2) once, and both
convolution stages are 'valid' — i.e. gradients are also computed on the
zero-extension ring (the natural formulation for a fused band kernel).
Implemented with lax.conv_general_dilated so the oracle shares no code with
the Pallas kernel's shift-and-add formulation.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

HARRIS_K = 0.04

SOBEL_X = jnp.array([[-1.0, 0.0, 1.0], [-2.0, 0.0, 2.0], [-1.0, 0.0, 1.0]])
SOBEL_Y = SOBEL_X.T
BOX = jnp.ones((3, 3))


def _conv3_valid(img: jnp.ndarray, kern: jnp.ndarray) -> jnp.ndarray:
    out = lax.conv_general_dilated(
        img[None, None],
        kern[None, None].astype(img.dtype),
        window_strides=(1, 1),
        padding="VALID",
        # full f32 on every backend: a TPU's default convolution precision
        # rounds f32 operands to bf16, which would fail the oracle itself
        precision=lax.Precision.HIGHEST,
    )
    return out[0, 0]


def harris_ref(img: jnp.ndarray, k: float = HARRIS_K) -> jnp.ndarray:
    padded = jnp.pad(img, 2)
    ix = _conv3_valid(padded, SOBEL_X)   # (x+2, y+2)
    iy = _conv3_valid(padded, SOBEL_Y)
    sxx = _conv3_valid(ix * ix, BOX)     # (x, y)
    syy = _conv3_valid(iy * iy, BOX)
    sxy = _conv3_valid(ix * iy, BOX)
    det = sxx * syy - sxy * sxy
    trace = sxx + syy
    return det - k * trace * trace
