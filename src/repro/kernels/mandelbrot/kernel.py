"""Tunable Mandelbrot Pallas TPU kernel.

Compute-bound, zero input bytes: each grid step derives its pixel
coordinates from the block indices with broadcasted iota and runs the
fixed-trip escape loop on the VPU.  Tunables shape the grid exactly like
the add kernel (blocks (8*t_x*t_z, 128*t_y), region splits w_x/w_y with
clamped idempotent indices), and the body walks the block's t_z row
sub-tiles of (8*t_x, 128*t_y) pixels one at a time, so the escape loop's
live vectors are one sub-tile wide whatever the block height.

Mosaic constraints the body is written around: iota is integer-only (the
pixel indices are cast to float afterwards), and the loop carries start
from a vector computed from both pixel coordinates: a splat constant, or a
vector that varies along one axis only, gets a replicated layout that the
loop body's result cannot be relaid into.
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
from .ref import MAX_ITER, VIEW


def _mandel_kernel(
    o_ref, *, bm: int, tz: int, bn: int, x: int, y: int,
    steps_r: int, nblk_r: int, steps_c: int, nblk_c: int,
    max_iter: int, view,
):
    gi, gj = pl.program_id(0), pl.program_id(1)
    rb = clamped_index(gi // steps_r, gi % steps_r, steps_r, nblk_r)
    cb = clamped_index(gj // steps_c, gj % steps_c, steps_c, nblk_c)

    xmin, xmax, ymin, ymax = view
    dtype = o_ref.dtype
    cols = cb * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    cre = xmin + (cols.astype(dtype) + 0.5) * ((xmax - xmin) / y)

    def sub_tile(t, _):
        rows = (rb * tz + t) * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        cim = ymin + (rows.astype(dtype) + 0.5) * ((ymax - ymin) / x)
        zero = (cre + cim) * 0.0

        def body(_, state):
            zr, zi, count = state
            alive = zr * zr + zi * zi < 4.0
            zr2 = zr * zr - zi * zi + cre
            zi2 = 2.0 * zr * zi + cim
            return (
                jnp.where(alive, zr2, zr),
                jnp.where(alive, zi2, zi),
                count + alive.astype(dtype),
            )

        _, _, count = jax.lax.fori_loop(0, max_iter, body, (zero, zero, zero))
        o_ref[pl.ds(t * bm, bm), :] = count
        return ()

    jax.lax.fori_loop(0, tz, sub_tile, ())


def mandelbrot_pallas(
    x: int,
    y: int,
    g: KernelGeometry,
    max_iter: int = MAX_ITER,
    view=VIEW,
    dtype=jnp.float32,
) -> jnp.ndarray:
    rows = g.rows_step
    steps_r, nblk_r = split_grid(x, rows, g.wx)
    steps_c, nblk_c = split_grid(y, g.bn, g.wy)

    def idx(gi, gj):
        return (
            clamped_index(gi // steps_r, gi % steps_r, steps_r, nblk_r),
            clamped_index(gj // steps_c, gj % steps_c, steps_c, nblk_c),
        )

    return pl.pallas_call(
        lambda o: _mandel_kernel(
            o, bm=g.bm, tz=g.tz, bn=g.bn, x=x, y=y,
            steps_r=steps_r, nblk_r=nblk_r, steps_c=steps_c, nblk_c=nblk_c,
            max_iter=max_iter, view=view,
        ),
        grid=(g.wx * steps_r, g.wy * steps_c),
        in_specs=[],
        out_specs=pl.BlockSpec((rows, g.bn), idx),
        out_shape=jax.ShapeDtypeStruct((x, y), dtype),
        compiler_params=compiler_params(),
        interpret=use_interpret(),
    )()
