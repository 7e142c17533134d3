"""Config-validity layer: pre-screen geometries, classify failures.

Real autotuning spaces are full of configurations that cannot run (or should
never run): kernel_tuner marks these with a failure value instead of
crashing the search, and the paper's own space carries a workgroup
constraint for exactly this reason.  This module is the TPU analogue:

* :func:`validate_config` pre-screens a config's :class:`KernelGeometry`
  against the kernel's resource model BEFORE any compile — VMEM footprint,
  tile alignment/divisibility, grid bounds — and returns a structured reason
  string (``None`` when the config is runnable).
* :class:`InvalidMeasurement` is the penalty record a failing config maps to:
  ``float("inf")`` plus the reason and the stage it failed at
  (``validity`` pre-screen, ``compile``, or ``run``).  Searchers receive the
  ``inf`` through the ordinary ``tell`` path and keep proposing; the disk
  cache persists the reason alongside the penalty.
* :func:`fit_constraint` packages the pre-screen as a *named* SearchSpace
  constraint (stable id ``pallas_fit:<kernel>:<x>:<y>:<mb>:<grid>``) so
  constrained searchers only propose runnable configs while SMBO methods —
  which per the paper get no constraint specification — discover penalties
  empirically, and specs using the space still round-trip through JSON.

The VMEM footprint is each kernel's own model of what Mosaic allocates
(``KernelBenchSpec.vmem_bytes``, two buffers per pipelined block plus the
kernel body's temporaries), held against the same budget every
``pallas_call`` asks Mosaic for (``kernels.common.VMEM_LIMIT_BYTES``).  It
is not the analytical cost model's ``vmem_bytes``, which prices an idealized
tiling against a chip's physical VMEM.
"""

from __future__ import annotations

from dataclasses import dataclass
from math import ceil

from ..kernels.common import (
    VMEM_LIMIT_BYTES,
    Config,
    KernelBenchSpec,
    KernelGeometry,
    geometry_from_config,
    tiled_vmem_bytes,
)
from .workloads import PallasWorkload

#: default VMEM budget — the scoped limit every kernel compiles with
DEFAULT_VMEM_LIMIT = VMEM_LIMIT_BYTES
#: Mosaic's own scratch on top of the kernel's blocks and temporaries (a
#: described v5e needed at most 0.25 MiB more than the kernels' models)
MOSAIC_SCRATCH_BYTES = 1024 * 1024
#: max total grid steps: interpret mode walks the grid in Python, and even on
#: hardware a degenerate million-step grid is pure launch overhead.
DEFAULT_MAX_GRID = 65536
SUBLANES = 8    # f32 min tile rows
LANES = 128     # lane count (last-dim tile)


@dataclass(frozen=True)
class InvalidMeasurement:
    """Structured penalty for a config that cannot be (or failed to be)
    measured: served to searchers as ``float("inf")``, persisted to the
    measurement store with its reason."""

    reason: str
    stage: str = "validity"       # "validity" | "compile" | "run"
    penalty: float = float("inf")

    def to_meta(self) -> str:
        """Serialized form stored in the measurement-store metadata."""
        return f"{self.stage}:{self.reason}"

    @classmethod
    def from_meta(cls, meta: str) -> "InvalidMeasurement":
        stage, _, reason = meta.partition(":")
        if stage not in ("validity", "compile", "run"):
            stage, reason = "validity", meta
        return cls(reason=reason, stage=stage)


def vmem_footprint(bench: KernelBenchSpec, g: KernelGeometry, y: int) -> int:
    """VMEM bytes Mosaic allocates for one grid step on an image ``y``
    columns wide (full-width band kernels depend on it)."""
    if bench.vmem_bytes is not None:
        return bench.vmem_bytes(g, y) + MOSAIC_SCRATCH_BYTES
    return tiled_vmem_bytes(bench, g) + MOSAIC_SCRATCH_BYTES


def grid_steps(g: KernelGeometry, x: int, y: int) -> int:
    """Total pipeline steps of the clamped region-split grid (see
    kernels/common.split_grid): (wx * steps_r) * (wy * steps_c)."""
    steps_r = ceil(ceil(x / g.wx) / g.rows_step)
    steps_c = ceil(ceil(y / g.wy) / g.bn)
    return g.wx * steps_r * g.wy * steps_c


def validate_geometry(
    bench: KernelBenchSpec,
    g: KernelGeometry,
    x: int,
    y: int,
    vmem_limit: int = DEFAULT_VMEM_LIMIT,
    max_grid: int = DEFAULT_MAX_GRID,
) -> str | None:
    """Reason the geometry cannot run on problem (x, y), or None if it can.

    Checks, in order of cheapness:
    * tile alignment — block dims must be multiples of the (8, 128) f32 tile
      (always true for config-derived geometries; guards custom spaces),
    * block-vs-image bounds — a block taller/wider than the (tile-aligned)
      image is >=50% padding waste; on hardware it also multiplies the VMEM
      bill for work that is entirely masked out,
    * grid bounds — degenerate splits must not explode the step count,
    * VMEM footprint — against the scoped limit the kernels compile with,
      the analogue of the paper's ``prod(workgroup) <= 256`` executability
      rule.
    """
    if g.bm % SUBLANES or g.bn % LANES:
        return f"align:block ({g.bm},{g.bn}) not a multiple of ({SUBLANES},{LANES})"
    x_pad = ceil(x / SUBLANES) * SUBLANES
    y_pad = ceil(y / LANES) * LANES
    if g.rows_step > x_pad or g.bn > y_pad:
        return (
            f"block:({g.rows_step},{g.bn}) exceeds padded image ({x_pad},{y_pad})"
        )
    n_steps = grid_steps(g, x, y)
    if n_steps > max_grid:
        return f"grid:{n_steps} steps > {max_grid}"
    vmem = vmem_footprint(bench, g, y)
    if vmem > vmem_limit:
        return f"vmem:{vmem} bytes > {vmem_limit}"
    return None


def validate_config(
    workload: PallasWorkload,
    cfg: Config,
    vmem_limit: int = DEFAULT_VMEM_LIMIT,
    max_grid: int = DEFAULT_MAX_GRID,
) -> str | None:
    """Pre-screen one config against a workload; reason string or None."""
    return validate_geometry(
        workload.bench,
        geometry_from_config(cfg),
        workload.x,
        workload.y,
        vmem_limit=vmem_limit,
        max_grid=max_grid,
    )


def fit_constraint(
    workload: PallasWorkload,
    vmem_limit: int = DEFAULT_VMEM_LIMIT,
    max_grid: int = DEFAULT_MAX_GRID,
):
    """The pre-screen as a named SearchSpace constraint predicate.

    The stable ``constraint_id`` lets TuningSpec serialization rebuild the
    constrained space by name in shard workers (resolved in
    ``repro.core.api._resolve_constraint``).
    """

    def fn(cfg: Config) -> bool:
        return validate_config(workload, cfg, vmem_limit, max_grid) is None

    fn.constraint_id = (
        f"pallas_fit:{workload.name}:{workload.x}:{workload.y}"
        f":{vmem_limit}:{max_grid}"
    )
    return fn
