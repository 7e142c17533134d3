"""Each kernel compiled by Mosaic for a described v5e chip at 8192x8192.

Interpret mode cannot see what the TPU compiler refuses (a scoped-VMEM
overrun, a vector layout Mosaic cannot relay), so these tests compile the
tuning path's kernels for a chip that is described, not attached: the
smallest geometry the validity screen admits, a middle one, and the tallest
band it admits.  A compile that passes here is not a chip run.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library at a time.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.add import kernel as add_kernel
from repro.kernels.add.ops import _add
from repro.kernels.harris import kernel as harris_kernel
from repro.kernels.harris.ops import _harris
from repro.kernels.mandelbrot import kernel as mandelbrot_kernel
from repro.kernels.mandelbrot.ops import _mandelbrot
from repro.kernels.mandelbrot.ref import MAX_ITER
from repro.pallas_bench import make_workload, validate_config

X = Y = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels lower through Mosaic, with JAX's persistent cache off (a
    described chip's executable cannot be read back here) and the traces
    dropped afterwards so no Mosaic-lowered program reaches a later test."""
    from jax.experimental.compilation_cache import compilation_cache

    for mod in (add_kernel, harris_kernel, mandelbrot_kernel):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def admitted_geometries(kernel: str) -> dict[str, dict]:
    """Smallest, middle and tallest-band (t_x, t_y, t_z) the screen admits
    at 8192x8192; among the tallest bands, the widest block."""
    w = make_workload(kernel, X, Y)
    ok = [
        dict(t_x=tx, t_y=ty, t_z=tz, w_x=1, w_y=1)
        for tx in range(1, 17)
        for ty in range(1, 17)
        for tz in range(1, 17)
        if validate_config(w, dict(t_x=tx, t_y=ty, t_z=tz, w_x=1, w_y=1, w_z=1))
        is None
    ]

    def size(c):
        return (c["t_x"] * c["t_z"], c["t_y"], c["t_x"])

    ok.sort(key=size)
    return {"smallest": ok[0], "middle": ok[len(ok) // 2], "tallest": ok[-1]}


def compile_for_chip(kernel: str, cfg: dict, one_chip):
    if kernel == "mandelbrot":
        # no array input to carry the placement: pin the output to the chip
        fn = jax.jit(
            lambda: _mandelbrot(x=X, y=Y, max_iter=MAX_ITER, **cfg),
            out_shardings=one_chip,
        )
        return fn.lower().compile()
    img = jax.ShapeDtypeStruct((X, Y), jnp.float32, sharding=one_chip)
    if kernel == "add":
        return jax.jit(lambda a, b: _add(a, b, **cfg)).lower(img, img).compile()
    return jax.jit(lambda a: _harris(a, **cfg)).lower(img).compile()


@pytest.mark.parametrize("which", ["smallest", "middle", "tallest"])
@pytest.mark.parametrize("kernel", ["add", "harris", "mandelbrot"])
def test_screened_in_geometry_compiles_for_v5e(kernel, which, one_chip, mosaic):
    cfg = admitted_geometries(kernel)[which]
    compiled = compile_for_chip(kernel, cfg, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
