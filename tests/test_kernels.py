"""Pallas kernels vs pure-jnp oracles across shape/dtype/config sweeps
(interpret mode on CPU; same pallas_call lowers to Mosaic on TPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    add,
    add_ref,
    harris,
    harris_ref,
    mandelbrot,
    mandelbrot_ref,
    reference_mismatch,
)

CONFIGS = [
    {},                                                   # defaults
    dict(t_x=2, t_y=1, t_z=2, w_x=2, w_y=2, w_z=2),
    dict(t_x=1, t_y=2, t_z=3, w_x=3, w_y=1, w_z=1),
    dict(t_x=4, t_y=1, t_z=1, w_x=1, w_y=4, w_z=4),
]

SHAPES = [(64, 128), (128, 256), (96, 384), (40, 128)]

# harris walks each band in tiles of 8 rows x 1024 lanes: besides the cases
# above, a width the tile does not divide (1152), widths narrower than a tile
# (640, and 600, no multiple of the 128-lane vreg either) and bands of 64 rows,
# one of them split in two row regions
HARRIS_SHAPES = SHAPES + [(72, 640), (136, 1152), (24, 600)]
HARRIS_CONFIGS = CONFIGS + [dict(t_x=8), dict(t_x=2, t_z=4, w_x=2)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("cfg", CONFIGS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_add_matches_ref(shape, cfg, dtype):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=shape), dtype)
    b = jnp.asarray(rng.normal(size=shape), dtype)
    out = add(a, b, cfg)
    assert out.dtype == dtype
    assert reference_mismatch("add", out, add_ref(a, b)) is None


@pytest.mark.parametrize("shape", HARRIS_SHAPES)
@pytest.mark.parametrize("cfg", HARRIS_CONFIGS)
def test_harris_matches_ref(shape, cfg):
    rng = np.random.default_rng(1)
    img = jnp.asarray(rng.normal(size=shape), jnp.float32)
    assert reference_mismatch("harris", harris(img, cfg), harris_ref(img)) is None


@pytest.mark.parametrize("shape", [(64, 128), (96, 256), (50, 130)])
@pytest.mark.parametrize("cfg", CONFIGS)
def test_mandelbrot_matches_ref(shape, cfg):
    """Escape-iteration counts are chaotic at the set boundary: FMA
    contraction differences legitimately move a handful of pixels by a few
    iterations -> 'discrete boundary' tolerance: >=99.5% exact, violations
    within +-4."""
    x, y = shape
    mismatch = reference_mismatch("mandelbrot", mandelbrot(x, y, cfg), mandelbrot_ref(x, y))
    assert mismatch is None, mismatch


def test_mandelbrot_interior_is_max_iter():
    out = np.asarray(mandelbrot(64, 64, max_iter=32))
    # the middle of the classic view contains the set -> full iteration count
    assert out.max() == 32


def test_add_odd_shapes_pad_correctly():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.normal(size=(56, 200)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(56, 200)), jnp.float32)
    out = add(a, b, dict(t_x=3, t_y=1, t_z=2, w_x=2, w_y=3))
    np.testing.assert_allclose(out, a + b, rtol=1e-6)


def test_reference_mismatch_names_what_failed():
    ref = jnp.zeros((8, 128), jnp.float32)
    assert reference_mismatch("add", ref + 1e-3, ref).startswith("exceeds")
    assert reference_mismatch("add", ref.at[0, 0].set(jnp.nan), ref) == "non-finite output"
    assert reference_mismatch("harris", ref[:4], ref).startswith("shape")
    one_off = ref.at[:1].set(5.0)            # 1/8 of pixels off by 5
    assert "exact" in reference_mismatch("mandelbrot", one_off, ref)
    assert reference_mismatch("mandelbrot", ref.at[0, 0].set(3.0), ref) is None
