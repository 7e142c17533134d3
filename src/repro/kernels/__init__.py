"""Tunable Pallas TPU kernels for the paper's three ImageCL benchmarks.

Each kernel directory holds:
    kernel.py — pl.pallas_call + BlockSpec implementation (tunable geometry)
    ops.py    — jitted public wrapper taking the paper's 6-param config
    ref.py    — pure-jnp oracle

Validation policy (tests/test_kernels.py): add and harris are compared with
assert_allclose across shape/dtype/config sweeps.  Mandelbrot's escape-time
loop is chaotic at the set boundary — 1-ulp FMA-contraction differences
between the two compiled programs legitimately shift a handful of pixels by
a few iterations — so its oracle check is '>= 99.5% pixels exactly equal,
violations within +-4 iterations' (the 'discrete boundary' tolerance class).

``reference_mismatch`` is that policy as one check, shared by the tests and
the on-chip smoke run.

``TUNABLE_KERNELS`` maps the cost-model workload names to real-runnable
entry points for the InterpretTimer measurement backend (examples/).
"""

import jax.numpy as jnp

from .add.ops import BENCH as _add_bench
from .add.ops import add
from .add.ref import add_ref
from .harris.ops import BENCH as _harris_bench
from .harris.ops import harris
from .harris.ref import harris_ref
from .mandelbrot.ops import BENCH as _mandelbrot_bench
from .mandelbrot.ops import mandelbrot
from .mandelbrot.ref import mandelbrot_ref

TUNABLE_KERNELS = {
    "add": add,
    "harris": harris,
    "mandelbrot": mandelbrot,
}

#: per-kernel resource/input descriptors consumed by the real-measurement
#: backend (repro.pallas_bench) — each kernel package owns its own entry.
KERNEL_BENCHES = {
    b.name: b for b in (_add_bench, _harris_bench, _mandelbrot_bench)
}


def reference_mismatch(kernel: str, out, ref) -> str | None:
    """``None`` when a kernel's output passes the validation policy above
    against its oracle's, else what failed.  Reduces on the arrays' device,
    so a deployment-size image never has to cross to the host."""
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    if out.shape != ref.shape:
        return f"shape {out.shape} != reference {ref.shape}"
    if not bool(jnp.isfinite(out).all()):
        return "non-finite output"
    err = jnp.abs(out - ref)
    if kernel == "mandelbrot":
        exact = float((err == 0).mean())
        worst = float(err.max())
        if exact < 0.995 or worst > 4:
            return f"{exact:.5f} of pixels exact (need 0.995), worst {worst} (need <= 4)"
        return None
    if kernel == "harris":
        rel = float(err.max() / jnp.abs(ref).max())
        return None if rel < 1e-5 else f"max error {rel:.3g} of max |ref| (need < 1e-5)"
    excess = float((err - (1e-6 + 1e-6 * jnp.abs(ref))).max())
    return None if excess <= 0 else f"exceeds rtol=atol=1e-6 by {excess:.3g}"


__all__ = [
    "add",
    "add_ref",
    "harris",
    "harris_ref",
    "mandelbrot",
    "mandelbrot_ref",
    "reference_mismatch",
    "TUNABLE_KERNELS",
    "KERNEL_BENCHES",
]
