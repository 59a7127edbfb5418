"""Products in a named precision, for the plain references.

``highest`` is float32 (``Precision.HIGHEST``), as the configurations
state it. ``high`` is the control: three bfloat16 passes,
``a_hi*b_hi + a_hi*b_lo + a_lo*b_hi`` with ``hi``/``lo`` the bfloat16
head and tail of each operand, as the TPU computes ``Precision.HIGH``.
Written out, so that it means the same on any backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")


def product(fn, a, b, precision: str):
    """``fn(a, b, Precision.HIGHEST)`` in the named precision."""
    exact = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return fn(a, b, exact)

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
    return fn(a_hi, b_hi, exact) + fn(a_hi, b_lo, exact) + fn(a_lo, b_hi,
                                                               exact)
