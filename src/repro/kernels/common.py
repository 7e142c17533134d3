"""Shared helpers for the tunable Pallas TPU kernels.

Kernel geometry mirrors the cost model (repro.costmodel.kernel_cost):

    bm = 8 * t_x          block rows
    bn = 128 * t_y        block cols
    t_z                   row coarsening (row-tiles per grid step)
    w_x, w_y              region splits (grid decomposition)
    w_z                   pipeline depth — the Pallas/Mosaic pipeliner
                          double-buffers every block, so w_z only enters
                          the cost model

Region splits use *clamped block indices*: the grid is
(w_x * steps_r, w_y * steps_c) where steps cover ceil-divided padded
regions; indices past the edge clamp to the last block, which makes the
duplicated writes idempotent and keeps every (config x shape) combination
legal — matching the cost model's padding-waste semantics.

On the CPU backend kernels run with ``interpret=True`` (tests, rehearsals);
on a TPU the same pallas_call lowers to Mosaic.  Every pallas_call asks
Mosaic for :data:`VMEM_LIMIT_BYTES` of scoped VMEM, and the validity screen
(``repro.pallas_bench.validity``) admits a geometry only when the kernel's
own footprint model (``KernelBenchSpec.vmem_bytes``) fits that same budget.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from math import ceil
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.pallas import tpu as pltpu

Config = dict

#: scoped VMEM every kernel asks Mosaic for, and the validity screen's limit.
#: Half of the v5e's 128 MiB physical VMEM: Mosaic's own default (16 MiB)
#: refuses mid-sized blocks, while the physical figure leaves no room for the
#: compiler's internal scratch and register spills.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


@dataclass(frozen=True)
class KernelGeometry:
    bm: int
    bn: int
    tz: int
    wx: int
    wy: int
    wz: int

    @property
    def rows_step(self) -> int:
        return self.bm * self.tz


@dataclass(frozen=True)
class KernelBenchSpec:
    """What a kernel package publishes to the real-measurement backend
    (:mod:`repro.pallas_bench`): its VMEM model, which the validity screen
    holds against :data:`VMEM_LIMIT_BYTES`, plus the two callables the bench
    harness needs — deterministic input materialization and the jitted
    entry point.

    The default VMEM model is :func:`tiled_vmem_bytes` (blocks of
    ``(rows_step, bn)`` tiles, ``scratch_tiles`` in-kernel ``(bm, bn)``
    temporaries).  A kernel whose blocks are shaped otherwise passes its own
    ``vmem_bytes(geometry, y)``.

    ``make_inputs(x, y, seed)`` must be a pure function of its arguments so
    shard workers rebuild bit-identical problems from a JSON spec alone.  It
    draws on the device the caller's ``jax.default_device`` names (see
    :func:`standard_normal_images`), so the identity holds between processes
    on one backend; across backends the images may differ in their last ulps.
    ``run(inputs, cfg, x, y)`` returns the (possibly still in-flight) device
    array; the harness owns fencing and timing.  ``wz_in_program`` records
    whether ``w_z`` changes the compiled program — today the Pallas/Mosaic
    pipeliner owns buffer counts (see module docstring), so configs differing
    only in ``w_z`` share one compilation-cache entry.  ``program_shape(
    geometry, x, y)`` optionally describes the program a geometry compiles
    to, as a dict of numbers the measurement writes onto its ``compile``
    stage event (harris: the band its body is unrolled over and the rows
    its entry pads).
    """

    name: str
    n_inputs: int
    make_inputs: Callable[[int, int, int], tuple] = field(repr=False, default=None)
    run: Callable[..., object] = field(repr=False, default=None)
    n_outputs: int = 1
    scratch_tiles: int = 0
    bpe: int = 4
    wz_in_program: bool = False
    vmem_bytes: Callable[[KernelGeometry, int], int] | None = field(
        repr=False, default=None
    )
    program_shape: Callable[[KernelGeometry, int, int], dict] | None = field(
        repr=False, default=None
    )


#: pixels, over all the images, one step of the draw's loop fills.  A block
#: this small keeps the draw's compile short at any image size: on a v5e host
#: one draw of two whole 8192x8192 images compiles for about 6 s, once per chip
DRAW_PIXELS = 1 << 16


@partial(jax.jit, static_argnames=("x", "y", "n"))
def _normal_images(seed, *, x: int, y: int, n: int) -> tuple:
    key = jax.random.key(seed, impl="threefry2x32")
    rows = max(1, min(x, DRAW_PIXELS // (n * y)))

    def fill(i, images):
        # the last block is clamped to the images' edge: where rows does not
        # divide x it draws again rows the block before it filled
        top = jnp.minimum(i * rows, x - rows)
        block = jax.random.normal(jax.random.fold_in(key, i), (n, rows, y), jnp.float32)
        return tuple(
            lax.dynamic_update_slice(img, block[j], (top, 0)) for j, img in enumerate(images)
        )

    empty = tuple(jnp.zeros((x, y), jnp.float32) for _ in range(n))
    return lax.fori_loop(0, -(-x // rows), fill, empty)


def standard_normal_images(x: int, y: int, seed: int, n: int) -> tuple:
    """``n`` f32 ``(x, y)`` images of standard-normal pixels, drawn from
    ``seed`` by one jitted program on the device the caller's
    ``jax.default_device`` names: nothing is drawn on the host or copied to
    the device.  The key names its PRNG (threefry2x32), so a process-wide
    ``jax_default_prng_impl`` does not change the data.  The images are
    filled in place, a block of rows at a time (about :data:`DRAW_PIXELS`
    pixels over all ``n``), each block one draw from the key folded with the
    block's index.  The same arguments give bit-identical images on one
    backend; a CPU and a TPU may differ in the last ulps."""
    return _normal_images(np.uint32(seed), x=x, y=y, n=n)


def tiled_vmem_bytes(bench: KernelBenchSpec, g: KernelGeometry) -> int:
    """VMEM Mosaic allocates for a kernel tiled in ``(rows_step, bn)``
    blocks: two pipeline buffers per input and output block, plus the
    kernel's ``(bm, bn)`` temporaries."""
    blocks = 2 * (bench.n_inputs + bench.n_outputs) * g.rows_step * g.bn
    return (blocks + bench.scratch_tiles * g.bm * g.bn) * bench.bpe


def compiler_params() -> pltpu.CompilerParams:
    """Mosaic options every kernel of this package compiles with."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def geometry_from_config(cfg: Config) -> KernelGeometry:
    return KernelGeometry(
        bm=8 * cfg.get("t_x", 1),
        bn=128 * cfg.get("t_y", 1),
        tz=cfg.get("t_z", 1),
        wx=cfg.get("w_x", 1),
        wy=cfg.get("w_y", 1),
        wz=cfg.get("w_z", 1),
    )


def split_grid(extent: int, block: int, splits: int) -> tuple[int, int]:
    """(steps_per_region, n_blocks_total) for a clamped region split."""
    region = ceil(extent / splits)
    steps = ceil(region / block)
    n_blocks = ceil(extent / block)
    return steps, n_blocks


def clamped_index(region: int, local: int, steps: int, n_blocks: int) -> int:
    """Block index for (region, local step), clamped to the last real block.

    Written with jnp maximum/minimum so it traces inside index_maps.
    """
    return jnp.minimum(region * steps + local, n_blocks - 1)


def use_interpret() -> bool:
    """Pallas interpret mode on the CPU backend; compiled Mosaic on a TPU.

    Any other backend raises: these kernels lower only through Mosaic, and
    interpreting them there would time the interpreter, not the kernel.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas path for backend {backend!r}: the kernels compile for a "
        "TPU and run interpreted on the CPU backend only"
    )
