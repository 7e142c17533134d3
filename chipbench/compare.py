"""The comparison that decides the kernel layer's share of ``correct``.

A copy of the policy of the program's ``kernels.reference_mismatch`` for
the image kernels (add, harris), turned into a number so that the run can
print it beside its limit: the largest absolute difference from the
reference, as a share of the reference's largest magnitude.  A shape
mismatch or a non-finite output reads ``inf``.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def out_err(out, ref) -> float:
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    if out.shape != ref.shape or not bool(jnp.isfinite(out).all()):
        return float("inf")
    scale = float(jnp.abs(ref).max())
    err = float(jnp.abs(out - ref).max())
    return err / scale if scale > 0 else err


def input_flaws(inputs, n: int, shape: tuple[int, int]) -> int:
    """How many of the ``n`` images a program ran on are not the configured
    problem's: missing, of another shape or type than f32 ``shape``, or with
    a mean or a standard deviation that is not the standard normal's to
    within 6 / sqrt(pixels), some six to eight standard errors."""
    flaws = abs(len(inputs) - n)
    for a in inputs[:n]:
        if tuple(a.shape) != tuple(shape) or a.dtype != jnp.float32:
            flaws += 1
            continue
        tol = 6.0 / math.sqrt(a.size)
        flaws += abs(float(jnp.mean(a))) > tol or abs(float(jnp.std(a)) - 1.0) > tol
    return flaws
