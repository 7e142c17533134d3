"""Tunable Harris-corner-detection Pallas TPU kernel.

TPU-native stencil strategy (DESIGN.md 2.1): the grid walks full-width row
*bands* of rows_step = 8*t_x*t_z rows.  The 2-row halo each band needs
(3x3 Sobel then 3x3 box = radius 2) is fetched through two extra 8-row
BlockSpecs of the same input whose index maps point at the neighbouring
8-row slabs — no overlapping BlockSpec tricks, no redundant full-band
reads.  Full image width lives in VMEM, so there is no horizontal DMA halo
at all — this is the part that differs most from the paper's OpenCL
kernel, where work-groups tile both axes; see DESIGN.md 'what changed'.

Inside a grid step the kernel first stages the band, with the halo slabs
above and below it, into a VMEM scratch of (rows_step + 16) x (y + 256)
pixels, 8 rows at a time: 128 zero lanes on either side stand in for the
column halo, and halo slabs past the image's edge are staged as zeros.  It
then walks the band in output tiles of :data:`TILE_ROWS` x
:func:`tile_lanes` pixels with ``lax.fori_loop``.  Each tile loads one
aligned window from the scratch, the three 8-row slabs around its rows and
128 lanes either side of its columns, and computes on 8-row values only:
its rows shifted by -2..2 come from sublane rotations of the slabs, its
columns' neighbours from lane rotations.  Mosaic thus compiles one tile's
body whatever the band's height, and the body's size depends on the image
width only up to :data:`TILE_LANES`.

Row-region splits (w_x) reorder the band traversal with clamped indices.
The 3x3 Sobel and box sums are computed in separable form, a pass down the
rows then one along them: MXU-free, pure VPU and XLU work, like the cost
model assumes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import (
    KernelGeometry,
    clamped_index,
    compiler_params,
    split_grid,
    use_interpret,
)
from .ref import HARRIS_K


#: rows of one output tile: one f32 sublane tile, which divides every band
TILE_ROWS = 8
#: lanes of one output tile where the image is at least this wide
TILE_LANES = 1024
#: zero lanes staged on either side of the band: one lane tile, so that every
#: window a tile loads starts on a lane tile
MARGIN = 128
#: rows each side of an output row that its value depends on (3x3 Sobel, then
#: a 3x3 box sum)
HALO = 2
#: rows of the window a tile loads: its own 8 and the 8-row slabs above and
#: below it, which hold its halo rows
WINDOW_ROWS = 3 * TILE_ROWS


def tile_lanes(y: int) -> int:
    """Lanes of one output tile on an image ``y`` columns wide."""
    return min(TILE_LANES, y)


def window_pixels(y: int) -> int:
    """Pixels of one output tile with its halo rows and margin lanes, the
    extent that the tile's body is unrolled over."""
    return (TILE_ROWS + 2 * HALO) * (tile_lanes(y) + 2 * MARGIN)


def _row_shift(above, mid, below, d: int):
    """Row ``i`` of the result is row ``i + d`` of ``mid``, reaching into the
    slab ``above`` or ``below`` it where that leaves ``mid``."""
    if d == 0:
        return mid
    n = mid.shape[0]
    i = lax.broadcasted_iota(jnp.int32, mid.shape, 0)
    if d > 0:
        return jnp.where(i < n - d, pltpu.roll(mid, n - d, 0), pltpu.roll(below, n - d, 0))
    return jnp.where(i >= -d, pltpu.roll(mid, -d, 0), pltpu.roll(above, -d, 0))


def _lane_shift(a, d: int):
    """Column ``j`` of the result is column ``j + d`` of ``a``, wrapping."""
    return pltpu.roll(a, (-d) % a.shape[1], 1)


def _harris_tile(above, mid, below, k: float):
    """Harris response of the rows ``mid`` from them and the slabs around
    them, in their shape; exact in the columns 2 or more in from its edges,
    where the lane rotations have not wrapped.  Each Sobel gradient is a
    [1, 2, 1] sum one way and a difference the other, with the opposite sign
    of the reference's; the response uses only their products, where the
    signs cancel."""
    rows = {d: _row_shift(above, mid, below, d) for d in range(-HALO, HALO + 1)}
    ix, iy = {}, {}
    for e in (-1, 0, 1):   # the gradients one row above, at and below each output row
        smooth = rows[e - 1] + 2.0 * rows[e] + rows[e + 1]
        diff = rows[e - 1] - rows[e + 1]
        ix[e] = _lane_shift(smooth, -1) - _lane_shift(smooth, 1)
        iy[e] = _lane_shift(diff, -1) + 2.0 * diff + _lane_shift(diff, 1)

    def box(p):
        col = p[-1] + p[0] + p[1]
        return _lane_shift(col, -1) + col + _lane_shift(col, 1)

    sxx = box({e: ix[e] * ix[e] for e in ix})
    syy = box({e: iy[e] * iy[e] for e in iy})
    sxy = box({e: ix[e] * iy[e] for e in ix})
    det = sxx * syy - sxy * sxy
    trace = sxx + syy
    return det - k * trace * trace


def _harris_kernel(
    top_ref, mid_ref, bot_ref, o_ref, band_ref,
    *, rows: int, steps_r: int, nblk_r: int, k: float,
):
    gi = pl.program_id(0)
    ri, li = gi // steps_r, gi % steps_r
    rb = clamped_index(ri, li, steps_r, nblk_r)

    y = mid_ref.shape[1]
    width = band_ref.shape[1]
    right = MARGIN + y // MARGIN * MARGIN    # first lane tile the image leaves
    zeros = jnp.zeros((TILE_ROWS, MARGIN), band_ref.dtype)
    tail = jnp.zeros((TILE_ROWS, width - right), band_ref.dtype)

    def stage(slab, pixels):
        # band_ref row 8 + i holds the band's row i, lane MARGIN + j column j
        r = pl.ds(pl.multiple_of(slab * TILE_ROWS, TILE_ROWS), TILE_ROWS)
        band_ref[r, pl.ds(0, MARGIN)] = zeros
        band_ref[r, pl.ds(right, width - right)] = tail
        band_ref[r, pl.ds(MARGIN, y)] = pixels

    # the halo slabs, zeroed at the image boundary (clamped neighbour = wrong rows)
    stage(0, jnp.where(rb == 0, jnp.zeros_like(top_ref), top_ref[...]))
    stage(rows // TILE_ROWS + 1, jnp.where(rb == nblk_r - 1, jnp.zeros_like(bot_ref), bot_ref[...]))

    def stage_mid(s, carry):
        stage(s + 1, mid_ref[pl.ds(pl.multiple_of(s * TILE_ROWS, TILE_ROWS), TILE_ROWS), :])
        return carry

    lax.fori_loop(0, rows // TILE_ROWS, stage_mid, ())

    lanes = tile_lanes(y)
    n_full, rem = divmod(y, lanes)

    def tile(r, c, w):
        # the window's row 8 is the tile's first row, its lane MARGIN the tile's first column
        span = -(-w // MARGIN) * MARGIN + 2 * MARGIN
        win = band_ref[pl.ds(r, WINDOW_ROWS), pl.ds(c, span)]
        out = _harris_tile(
            win[:TILE_ROWS], win[TILE_ROWS : 2 * TILE_ROWS], win[2 * TILE_ROWS :], k
        )
        o_ref[pl.ds(r, TILE_ROWS), pl.ds(c, w)] = out[:, MARGIN : MARGIN + w]

    def row_tile(t, carry):
        r = pl.multiple_of(t * TILE_ROWS, TILE_ROWS)

        def lane_tile(j, carry):
            tile(r, pl.multiple_of(j * lanes, MARGIN), lanes)
            return carry

        lax.fori_loop(0, n_full, lane_tile, ())
        if rem:   # the narrower last tile where the tile does not divide the width
            tile(r, n_full * lanes, rem)
        return carry

    lax.fori_loop(0, rows // TILE_ROWS, row_tile, ())


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
        lambda t, m, b, o, band: _harris_kernel(
            t, m, b, o, band, rows=rows, steps_r=steps_r, nblk_r=nblk_r, k=k
        ),
        grid=(g.wx * steps_r,),
        in_specs=[
            pl.BlockSpec((8, y), top_idx),
            pl.BlockSpec((rows, y), mid_idx),
            pl.BlockSpec((8, y), bot_idx),
        ],
        out_specs=pl.BlockSpec((rows, y), mid_idx),
        out_shape=jax.ShapeDtypeStruct(img.shape, img.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows + 2 * TILE_ROWS, 2 * MARGIN + -(-y // MARGIN) * MARGIN), img.dtype)
        ],
        compiler_params=compiler_params(),
        interpret=use_interpret(),
    )(img, img, img)
