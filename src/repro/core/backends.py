"""Measurement-backend registry: ``make_measurement(name, **kwargs)``.

Mirrors the ``SEARCHERS`` registry for the evaluation side of the tuner, so
a :class:`~repro.core.api.TuningSpec` can name its backend declaratively and
the sharded session driver can rebuild the exact measurement in a worker
process.  Built-in backends:

* ``"costmodel"`` — the analytical TPU cost model with counter-based noise
  (``kernel=..., chip=..., seed=..., noise=...``); also provides the default
  :class:`SearchSpace` (executable configs) and the noise-free true optimum.
* ``"pallas"``    — REAL ``pl.pallas_call`` execution through
  :mod:`repro.pallas_bench` (compile-once-per-geometry cache, warmup +
  N-repeat fenced timing, validity pre-screen mapping failures to ``inf``
  penalties); name-serializable, so specs using it shard cleanly.  Interpret
  mode on CPU, Mosaic on TPU, selected automatically.
* ``"timing"``    — wall-clock of a real callable (``runner=..., warmup=...``),
  for custom objectives the ``pallas`` backend doesn't cover.
* ``"cached"``    — in-memory memoization of an ``inner`` backend (paper: a
  config is measured once during search).
* ``"disk"``      — persistent memoization of an ``inner`` backend through a
  measurement store (``store="json"|"sqlite"``, ``store_path=...``).

``inner`` is either a backend *name* (resolved recursively, with
``inner_kwargs``) or an already-built measurement instance.  Register custom
backends with :func:`register_backend`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

from .engine import DiskCachedMeasurement
from .measurement import (
    BaseMeasurement,
    CachedMeasurement,
    CallableMeasurement,
    TimingMeasurement,
)
from .space import SearchSpace


@dataclass(frozen=True)
class Backend:
    """A named measurement backend.

    ``make(kernel=..., seed=..., **kwargs)`` builds a measurement; backends
    that don't need the kernel id / seed accept and ignore them, so the
    session driver can call every backend uniformly.  ``default_space`` /
    ``true_optimum`` are optional hooks the costmodel backend provides so a
    spec can omit its space and records can carry the exact optimum.
    ``serializable`` marks whether specs using this backend can round-trip
    through JSON (a backend whose kwargs hold callables cannot be shipped to
    shard workers).  ``pipeline`` marks whether ``make`` accepts a
    ``pipeline_workers=`` kwarg (the staged compile-prefetch pipeline); the
    session driver refuses to silently drop the knob on backends without it.
    ``uses_device`` marks a backend whose measurements run on the
    accelerator: on a TPU host only the ``device`` executor may run it in
    parallel, since a chip belongs to one process.
    """

    name: str
    make: Callable[..., BaseMeasurement]
    default_space: Callable[..., SearchSpace] | None = None
    true_optimum: Callable[..., tuple[dict, float]] | None = None
    serializable: bool = True
    pipeline: bool = False
    uses_device: bool = False


BACKENDS: dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    BACKENDS[backend.name] = backend
    return backend


def make_measurement(name: str, **kwargs) -> BaseMeasurement:
    """Build a measurement backend by registry name."""
    if name not in BACKENDS:
        raise KeyError(f"unknown backend {name!r}; have {sorted(BACKENDS)}")
    return BACKENDS[name].make(**kwargs)


# --------------------------------------------------------------- costmodel


def _costmodel_parts(kernel: str, chip: str):
    # lazy import: core must stay importable without the costmodel package
    from ..costmodel import CHIPS, WORKLOADS

    if kernel not in WORKLOADS:
        raise KeyError(f"unknown kernel {kernel!r}; have {sorted(WORKLOADS)}")
    if chip not in CHIPS:
        raise KeyError(f"unknown chip {chip!r}; have {sorted(CHIPS)}")
    return WORKLOADS[kernel], CHIPS[chip]


def _make_costmodel(
    kernel: str = "harris", chip: str = "v5e", seed: int = 0, noise: bool = True
) -> BaseMeasurement:
    from ..costmodel import CostModelMeasurement

    w, c = _costmodel_parts(kernel, chip)
    return CostModelMeasurement(w, c, seed=seed, noise=noise)


def _costmodel_space(kernel: str = "harris", chip: str = "v5e", **_) -> SearchSpace:
    from ..costmodel import executable_space

    w, c = _costmodel_parts(kernel, chip)
    return executable_space(w, c)


def _costmodel_optimum(kernel: str = "harris", chip: str = "v5e", **_):
    from ..costmodel import true_optimum

    w, c = _costmodel_parts(kernel, chip)
    return true_optimum(w, c)


# ------------------------------------------------------------------ pallas


def _make_pallas(
    kernel: str = "add",
    seed: int = 0,
    *,
    x: int | None = None,
    y: int | None = None,
    input_seed: int = 0,
    repeats: int = 5,
    warmup: int = 1,
    vmem_limit: int | None = None,
    max_grid: int | None = None,
    validate: bool = True,
    pipeline_workers: int = 0,
    compile_cache: str | None = None,
) -> BaseMeasurement:
    # lazy import: core must stay importable without jax/pallas_bench
    from ..pallas_bench import (
        DEFAULT_MAX_GRID,
        DEFAULT_VMEM_LIMIT,
        DEFAULT_X,
        DEFAULT_Y,
        PallasMeasurement,
        make_workload,
    )

    workload = make_workload(
        kernel,
        x=x if x is not None else DEFAULT_X,
        y=y if y is not None else DEFAULT_Y,
        input_seed=input_seed,
    )
    return PallasMeasurement(
        workload,
        repeats=repeats,
        warmup=warmup,
        vmem_limit=vmem_limit if vmem_limit is not None else DEFAULT_VMEM_LIMIT,
        max_grid=max_grid if max_grid is not None else DEFAULT_MAX_GRID,
        validate=validate,
        pipeline_workers=pipeline_workers,
        compile_cache=compile_cache,
    )


def _pallas_space(kernel: str = "add", **kwargs) -> SearchSpace:
    from ..pallas_bench import (
        DEFAULT_MAX_GRID,
        DEFAULT_VMEM_LIMIT,
        DEFAULT_X,
        DEFAULT_Y,
        default_space,
    )

    return default_space(
        kernel,
        x=kwargs.get("x") or DEFAULT_X,
        y=kwargs.get("y") or DEFAULT_Y,
        vmem_limit=kwargs.get("vmem_limit") or DEFAULT_VMEM_LIMIT,
        max_grid=kwargs.get("max_grid") or DEFAULT_MAX_GRID,
    )


# --------------------------------------------------------------- wrappers


def _make_timing(
    kernel: str | None = None,
    seed: int = 0,
    *,
    runner: Callable,
    warmup: int = 1,
) -> BaseMeasurement:
    return TimingMeasurement(runner, warmup=warmup)


def _make_callable(
    kernel: str | None = None,
    seed: int = 0,
    *,
    fn: Callable,
    batch_fn: Callable | None = None,
) -> BaseMeasurement:
    return CallableMeasurement(fn, batch_fn=batch_fn)


def _resolve_inner(inner, inner_kwargs, kernel, seed) -> BaseMeasurement:
    if isinstance(inner, str):
        return make_measurement(inner, kernel=kernel, seed=seed, **(inner_kwargs or {}))
    if isinstance(inner, BaseMeasurement):
        return inner
    raise TypeError(
        f"inner must be a backend name or a BaseMeasurement, got {type(inner).__name__}"
    )


def _make_cached(
    kernel: str | None = None,
    seed: int = 0,
    *,
    inner,
    inner_kwargs: dict | None = None,
) -> BaseMeasurement:
    return CachedMeasurement(_resolve_inner(inner, inner_kwargs, kernel, seed))


def _make_disk(
    kernel: str | None = None,
    seed: int = 0,
    *,
    inner,
    inner_kwargs: dict | None = None,
    store="json",
    store_path: str | None = None,
    prefix: str | None = None,
) -> BaseMeasurement:
    from .stores import make_store

    if isinstance(store, str):
        store = make_store(store, store_path)
    if prefix is None:
        prefix = f"{kernel or 'objective'}/seed={seed}"
    return DiskCachedMeasurement(
        _resolve_inner(inner, inner_kwargs, kernel, seed), store, prefix
    )


register_backend(
    Backend(
        name="costmodel",
        make=_make_costmodel,
        default_space=_costmodel_space,
        true_optimum=_costmodel_optimum,
    )
)
register_backend(
    Backend(
        name="pallas",
        make=_make_pallas,
        default_space=_pallas_space,
        pipeline=True,
        uses_device=True,
    )
)
register_backend(Backend(name="timing", make=_make_timing, serializable=False))
register_backend(Backend(name="callable", make=_make_callable, serializable=False))
register_backend(Backend(name="cached", make=_make_cached))
register_backend(Backend(name="disk", make=_make_disk))
