"""Tunable elementwise-add Pallas TPU kernel.

Memory-bound: the tunables set the HBM->VMEM streaming geometry.
Block = (bm * t_z, bn); the kernel body walks t_z row sub-tiles (the
'thread coarsening' analogue — one grid step amortizes pipeline overhead
over t_z tiles).  Region splits (w_x, w_y) reorder the grid traversal with
clamped indices (see kernels/common.py).
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


def _add_kernel(a_ref, b_ref, o_ref, *, bm: int, tz: int):
    def body(i, _):
        sl = pl.ds(i * bm, bm)
        o_ref[sl, :] = a_ref[sl, :] + b_ref[sl, :]
        return ()

    jax.lax.fori_loop(0, tz, body, ())


def add_pallas(a: jnp.ndarray, b: jnp.ndarray, g: KernelGeometry) -> jnp.ndarray:
    x, y = a.shape
    rows = g.rows_step
    steps_r, nblk_r = split_grid(x, rows, g.wx)
    steps_c, nblk_c = split_grid(y, g.bn, g.wy)

    def idx(gi, gj):
        ri, li = gi // steps_r, gi % steps_r
        rj, lj = gj // steps_c, gj % steps_c
        return (
            clamped_index(ri, li, steps_r, nblk_r),
            clamped_index(rj, lj, steps_c, nblk_c),
        )

    spec = pl.BlockSpec((rows, g.bn), idx)
    return pl.pallas_call(
        lambda a_ref, b_ref, o_ref: _add_kernel(a_ref, b_ref, o_ref, bm=g.bm, tz=g.tz),
        grid=(g.wx * steps_r, g.wy * steps_c),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        compiler_params=compiler_params(),
        interpret=use_interpret(),
    )(a, b)
