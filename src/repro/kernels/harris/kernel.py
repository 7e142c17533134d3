"""Tunable Harris-corner-detection Pallas TPU kernel.

TPU-native stencil strategy (DESIGN.md 2.1): the grid walks full-width row
*bands* of rows_step = 8*t_x*t_z rows.  The 2-row halo each band needs
(3x3 Sobel then 3x3 box = radius 2) is fetched through two extra 8-row
BlockSpecs of the same input whose index maps point at the neighbouring
8-row slabs — no overlapping BlockSpec tricks, no redundant full-band
reads.  Column halo is materialized in-register by zero-padding the band
(full image width lives in VMEM, so there is no horizontal DMA halo at
all — this is the part that differs most from the paper's OpenCL kernel,
where work-groups tile both axes; see DESIGN.md 'what changed').

Row-region splits (w_x) reorder the band traversal with clamped indices.
The 3x3 convolutions are computed as shift-and-add over the VMEM band —
MXU-free, pure VPU work, like the cost model assumes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import (
    KernelGeometry,
    clamped_index,
    compiler_params,
    split_grid,
    use_interpret,
)
from .ref import HARRIS_K


def _shift_conv3(w: jnp.ndarray, kern) -> jnp.ndarray:
    """3x3 'valid' convolution of a zero-padded window via shift-and-add.

    w: (H + 2, W + 2) -> (H, W).  kern is a 3x3 nested tuple of floats.
    Matches conv semantics (kernel flipped), i.e. output[i,j] =
    sum_{di,dj} kern[di][dj] * w[i + 2 - di, j + 2 - dj]... simplified here
    because all our kernels are symmetric or antisymmetric: we use
    cross-correlation and pass pre-flipped kernels (Sobel/box are their own
    flip up to sign conventions used consistently with the oracle).
    """
    h, wd = w.shape[0] - 2, w.shape[1] - 2
    out = jnp.zeros((h, wd), dtype=w.dtype)
    for di in range(3):
        for dj in range(3):
            c = kern[di][dj]
            if c == 0.0:
                continue
            out = out + c * w[di : di + h, dj : dj + wd]
    return out


# cross-correlation forms that reproduce conv(SOBEL_X/Y) in the oracle:
# conv flips the kernel; SOBEL_X flipped = -SOBEL_X mirrored -> precomputed.
_SOBEL_X_XCORR = ((1.0, 0.0, -1.0), (2.0, 0.0, -2.0), (1.0, 0.0, -1.0))
_SOBEL_Y_XCORR = ((1.0, 2.0, 1.0), (0.0, 0.0, 0.0), (-1.0, -2.0, -1.0))
_BOX = ((1.0, 1.0, 1.0), (1.0, 1.0, 1.0), (1.0, 1.0, 1.0))


def _harris_kernel(
    top_ref, mid_ref, bot_ref, o_ref, *, rows: int, steps_r: int, nblk_r: int, k: float
):
    gi = pl.program_id(0)
    ri, li = gi // steps_r, gi % steps_r
    rb = clamped_index(ri, li, steps_r, nblk_r)

    y = mid_ref.shape[1]
    top2 = top_ref[6:8, :]
    bot2 = bot_ref[0:2, :]
    # zero the halo at the image boundary (clamped neighbour = wrong rows)
    top2 = jnp.where(rb == 0, jnp.zeros_like(top2), top2)
    bot2 = jnp.where(rb == nblk_r - 1, jnp.zeros_like(bot2), bot2)

    band = jnp.concatenate([top2, mid_ref[...], bot2], axis=0)  # (rows+4, y)
    band = jnp.pad(band, ((0, 0), (2, 2)))                      # (rows+4, y+4)

    ix = _shift_conv3(band, _SOBEL_X_XCORR)   # (rows+2, y+2)
    iy = _shift_conv3(band, _SOBEL_Y_XCORR)
    sxx = _shift_conv3(ix * ix, _BOX)         # (rows, y)
    syy = _shift_conv3(iy * iy, _BOX)
    sxy = _shift_conv3(ix * iy, _BOX)
    det = sxx * syy - sxy * sxy
    trace = sxx + syy
    o_ref[...] = det - k * trace * trace


def harris_pallas(img: jnp.ndarray, g: KernelGeometry, k: float = HARRIS_K) -> jnp.ndarray:
    x, y = img.shape
    rows = g.rows_step
    if x % rows:
        raise ValueError(f"harris_pallas: rows {x} must divide rows_step {rows} (ops.py pads)")
    if rows % 8:
        raise ValueError("rows_step must be a multiple of 8")
    steps_r, nblk_r = split_grid(x, rows, g.wx)
    sub = rows // 8           # 8-row slabs per band
    nslab = x // 8

    def mid_idx(gi):
        ri, li = gi // steps_r, gi % steps_r
        return (clamped_index(ri, li, steps_r, nblk_r), 0)

    def top_idx(gi):
        rb = mid_idx(gi)[0]
        return (jnp.maximum(rb * sub - 1, 0), 0)

    def bot_idx(gi):
        rb = mid_idx(gi)[0]
        return (jnp.minimum((rb + 1) * sub, nslab - 1), 0)

    return pl.pallas_call(
        lambda t, m, b, o: _harris_kernel(
            t, m, b, o, rows=rows, steps_r=steps_r, nblk_r=nblk_r, k=k
        ),
        grid=(g.wx * steps_r,),
        in_specs=[
            pl.BlockSpec((8, y), top_idx),
            pl.BlockSpec((rows, y), mid_idx),
            pl.BlockSpec((8, y), bot_idx),
        ],
        out_specs=pl.BlockSpec((rows, y), mid_idx),
        out_shape=jax.ShapeDtypeStruct(img.shape, img.dtype),
        compiler_params=compiler_params(),
        interpret=use_interpret(),
    )(img, img, img)
