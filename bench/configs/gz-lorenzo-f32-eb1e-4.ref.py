"""Plain reference of the compressed collective deployment.

The allreduce's exact answer is the sum of the ranks' inputs, and the
codec round trip's is its input: the configuration guarantees the
program's answers within ``eb`` of these, element by element. Both are
written in plain ``jax.numpy`` on one device and import nothing of the
program. ``dtype`` selects the precision the reference computes in: the
control runs it in bfloat16, the precision below the f32 the
configuration states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def allreduce_sum(rows, dtype=jnp.float32):
    """rows: (ranks, n) -> (n,) f32, the sum over ranks in ``dtype``."""
    return jnp.sum(rows.astype(dtype), axis=0, dtype=dtype).astype(jnp.float32)


def roundtrip(x, dtype=jnp.float32):
    """The exact round trip, x itself, held in ``dtype``."""
    return x.astype(dtype).astype(jnp.float32)


@jax.jit
def max_abs_gap(a, b):
    """max |a - b| over every element (a may carry a leading rank axis)."""
    return jnp.max(jnp.abs(a - b))
