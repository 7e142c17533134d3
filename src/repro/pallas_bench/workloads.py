"""Workload plumbing: materialize deterministic per-kernel problems.

A :class:`PallasWorkload` binds one kernel's :class:`KernelBenchSpec`
(published by the kernel package itself — see ``kernels/*/ops.py``) to a
concrete image size and input seed.  Everything a workload needs is derivable
from ``(kernel, x, y, input_seed)``, i.e. from a JSON-serialized
:class:`~repro.core.api.TuningSpec` alone, so shard workers rebuild
bit-identical problems without any live objects crossing process boundaries.

Input images are drawn from ``stable_seed(...)`` by a jitted ``jax.random``
program on the device the caller's ``jax.default_device`` names
(``kernels.common.standard_normal_images``): nothing is drawn on the host or
copied over.  ``stable_seed`` is crc32-based and process-invariant (Python's
``hash`` is salted), the same discipline the matrix runner uses for
experiment seeds.  The identity holds within a backend: a CPU and a TPU draw
the same images up to the last ulps.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from ..core.runner import stable_seed
from ..kernels import KERNEL_BENCHES
from ..kernels.common import Config, KernelBenchSpec, geometry_from_config, use_interpret

#: default problem size — small enough that interpret mode (Python-level
#: grid execution) measures a config in milliseconds, large enough that the
#: tunable geometry actually changes the grid.
DEFAULT_X = 128
DEFAULT_Y = 256


@dataclass(frozen=True)
class PallasWorkload:
    """One kernel bound to a concrete problem: the unit pallas_bench measures."""

    bench: KernelBenchSpec = field(repr=False)
    x: int = DEFAULT_X
    y: int = DEFAULT_Y
    input_seed: int = 0

    @property
    def name(self) -> str:
        return self.bench.name

    def materialize(self) -> tuple:
        """Deterministic input arrays for this problem, on the caller's
        default device (pure function of the workload fields — any process
        on the same backend gets byte-identical data)."""
        seed = stable_seed("pallas_inputs", self.name, self.x, self.y, self.input_seed)
        return tuple(self.bench.make_inputs(self.x, self.y, seed))

    def run(self, inputs: tuple, cfg: Config):
        """Launch the kernel; returns the (possibly in-flight) device array.
        The measurement layer owns fencing and timing."""
        return self.bench.run(inputs, cfg, self.x, self.y)

    def program_shape(self, cfg: Config) -> dict:
        """The kernel's description of the program cfg compiles to at this
        size (``KernelBenchSpec.program_shape``); empty where it gives none."""
        shape = self.bench.program_shape
        return {} if shape is None else shape(geometry_from_config(cfg), self.x, self.y)

    def interpret(self) -> bool:
        """Whether ``pl.pallas_call`` runs in interpret mode here (CPU) —
        the kernels decide via ``kernels.common.use_interpret``."""
        return use_interpret()


def make_workload(
    kernel: str,
    x: int = DEFAULT_X,
    y: int = DEFAULT_Y,
    input_seed: int = 0,
) -> PallasWorkload:
    """Resolve a kernel id to a measurable workload."""
    if kernel not in KERNEL_BENCHES:
        raise KeyError(
            f"unknown pallas kernel {kernel!r}; have {sorted(KERNEL_BENCHES)}"
        )
    if x < 8 or y < 128:
        raise ValueError(
            f"problem size ({x}, {y}) below the minimum f32 tile (8, 128)"
        )
    return PallasWorkload(bench=KERNEL_BENCHES[kernel], x=int(x), y=int(y),
                          input_seed=int(input_seed))
