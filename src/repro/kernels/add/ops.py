"""Jitted public wrapper for the tunable add kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import Config, KernelBenchSpec, geometry_from_config, standard_normal_images
from .kernel import add_pallas


@partial(jax.jit, static_argnames=("t_x", "t_y", "t_z", "w_x", "w_y", "w_z"))
def _add(a, b, *, t_x=1, t_y=1, t_z=1, w_x=1, w_y=1, w_z=1):
    g = geometry_from_config(
        dict(t_x=t_x, t_y=t_y, t_z=t_z, w_x=w_x, w_y=w_y, w_z=w_z)
    )
    return add_pallas(a, b, g)


def add(a: jnp.ndarray, b: jnp.ndarray, config: Config | None = None) -> jnp.ndarray:
    """Tunable-config elementwise add: config holds the paper's 6 params."""
    cfg = config or {}
    return _add(
        a,
        b,
        t_x=cfg.get("t_x", 1),
        t_y=cfg.get("t_y", 1),
        t_z=cfg.get("t_z", 1),
        w_x=cfg.get("w_x", 1),
        w_y=cfg.get("w_y", 1),
        w_z=cfg.get("w_z", 1),
    )


#: resource + input model for the real-measurement backend (pallas_bench)
BENCH = KernelBenchSpec(
    name="add",
    n_inputs=2,
    make_inputs=lambda x, y, seed: standard_normal_images(x, y, seed, 2),
    run=lambda inputs, cfg, x, y: add(inputs[0], inputs[1], cfg),
)
