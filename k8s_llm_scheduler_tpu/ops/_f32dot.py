"""Float32 products with a state inside a Pallas kernel, as the state-space
kernels (ops/gdn_scan.py, ops/ssd_scan.py) take them: three bfloat16
passes with float32 sums, which keep 16 bits of each operand's mantissa
at a third of the cost of a `highest` product."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def split(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    """a = hi + lo to 16 bits of mantissa, each half a bfloat16."""
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def dot3(a, b, dims) -> jax.Array:
    """a . b over `dims` in three bfloat16 passes with float32 sums."""
    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
    dot = functools.partial(jax.lax.dot_general, dimension_numbers=(dims, ((), ())),
                            preferred_element_type=jnp.float32)
    return dot(a_hi, b_hi) + (dot(a_lo, b_hi) + dot(a_hi, b_lo))
