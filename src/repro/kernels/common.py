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
from math import ceil
from typing import Callable

import jax
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
    shard workers rebuild bit-identical problems from a JSON spec alone.
    ``run(inputs, cfg, x, y)`` returns the (possibly still in-flight) device
    array; the harness owns fencing and timing.  ``wz_in_program`` records
    whether ``w_z`` changes the compiled program — today the Pallas/Mosaic
    pipeliner owns buffer counts (see module docstring), so configs differing
    only in ``w_z`` share one compilation-cache entry.
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
    import jax.numpy as jnp

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
