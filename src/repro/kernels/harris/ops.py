"""Jitted public wrapper for the tunable Harris kernel.

Pads rows to a multiple of the band height (zero padding — identical to the
oracle's boundary condition as long as the pad is >= the stencil radius,
which rows_step >= 8 always satisfies) and crops the result.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..common import Config, KernelBenchSpec, KernelGeometry, geometry_from_config
from .kernel import harris_pallas


@partial(jax.jit, static_argnames=("t_x", "t_y", "t_z", "w_x", "w_y", "w_z"))
def _harris(img, *, t_x=1, t_y=1, t_z=1, w_x=1, w_y=1, w_z=1):
    g = geometry_from_config(
        dict(t_x=t_x, t_y=t_y, t_z=t_z, w_x=w_x, w_y=w_y, w_z=w_z)
    )
    x, y = img.shape
    rows = g.rows_step
    x_pad = (-x) % rows
    padded = jnp.pad(img, ((0, x_pad), (0, 0)))
    out = harris_pallas(padded, g)
    return out[:x]


def harris(img: jnp.ndarray, config: Config | None = None) -> jnp.ndarray:
    cfg = config or {}
    return _harris(
        img,
        t_x=cfg.get("t_x", 1),
        t_y=cfg.get("t_y", 1),
        t_z=cfg.get("t_z", 1),
        w_x=cfg.get("w_x", 1),
        w_y=cfg.get("w_y", 1),
        w_z=cfg.get("w_z", 1),
    )


def _bench_inputs(x: int, y: int, seed: int) -> tuple:
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((x, y)), jnp.float32),)


#: full-width temporaries per band row that Mosaic keeps in VMEM (padded
#: band, gradients, their products, box sums, det/trace and the compiler's
#: copies of them), fitted to the smallest scoped-VMEM limit at which a
#: described v5e compiles bands of 8 to 128 rows at widths 256 to 8192
BAND_TEMPORARIES = 23


def _vmem_bytes(g: KernelGeometry, y: int) -> int:
    """Mosaic's VMEM for one band: two pipeline buffers each of the two
    8-row halo slabs, the band and the output band, all full-width, plus the
    full-width temporaries over the lane-padded, halo-extended width."""
    rows = g.rows_step
    blocks = 2 * (2 * 8 + 2 * rows) * y
    temporaries = BAND_TEMPORARIES * rows * (-(-(y + 4) // 128) * 128)
    return (blocks + temporaries) * 4


BENCH = KernelBenchSpec(
    name="harris",
    n_inputs=1,
    make_inputs=_bench_inputs,
    run=lambda inputs, cfg, x, y: harris(inputs[0], cfg),
    vmem_bytes=_vmem_bytes,
)
