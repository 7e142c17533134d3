"""Jitted public wrapper for the tunable Mandelbrot kernel."""

from __future__ import annotations

from functools import partial

import jax

from ..common import Config, KernelBenchSpec, geometry_from_config
from .kernel import mandelbrot_pallas
from .ref import MAX_ITER


@partial(jax.jit, static_argnames=("x", "y", "max_iter", "t_x", "t_y", "t_z", "w_x", "w_y", "w_z"))
def _mandelbrot(*, x, y, max_iter, t_x=1, t_y=1, t_z=1, w_x=1, w_y=1, w_z=1):
    g = geometry_from_config(
        dict(t_x=t_x, t_y=t_y, t_z=t_z, w_x=w_x, w_y=w_y, w_z=w_z)
    )
    return mandelbrot_pallas(x, y, g, max_iter=max_iter)


def mandelbrot(x: int, y: int, config: Config | None = None, max_iter: int = MAX_ITER):
    cfg = config or {}
    return _mandelbrot(
        x=x,
        y=y,
        max_iter=max_iter,
        t_x=cfg.get("t_x", 1),
        t_y=cfg.get("t_y", 1),
        t_z=cfg.get("t_z", 1),
        w_x=cfg.get("w_x", 1),
        w_y=cfg.get("w_y", 1),
        w_z=cfg.get("w_z", 1),
    )


#: generator kernel — no input arrays; the image size IS the problem.  The
#: escape loop keeps cre, cim, zr, zi, the count and their updates live per
#: (bm, bn) sub-tile: 8 tiles fit what a described v5e allocates
BENCH = KernelBenchSpec(
    name="mandelbrot",
    n_inputs=0,
    make_inputs=lambda x, y, seed: (),
    run=lambda inputs, cfg, x, y: mandelbrot(x, y, cfg),
    scratch_tiles=8,
)
