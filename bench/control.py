"""The control: the reference step in the precision just below the one the
configuration states, put in the program's place.

The program computes its distances and its update in float32 at
``Precision.HIGHEST``.  The next precision down is ``high``: three bf16
passes per float32 product (hi·hi + hi·lo + lo·hi, the lo·lo pass left
out), the step a later change would be tempted to take.  It is written out
here with explicit bf16 splits, so that it computes the same on the chip
and on the CPU, where ``Precision.HIGH`` would change nothing.  The check must judge this step not correct; see
``bench/calibrate.py``, which reads it on the chip.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["control_step", "dot_3pass"]


def _bf16(v: jax.Array) -> jax.Array:
    """``v`` rounded to bf16's 8 significant bits, kept in float32.
    ``reduce_precision`` is never folded away, as a float32 -> bf16 ->
    float32 round trip may be where the compiler allows excess precision."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def dot_3pass(a: jax.Array, b: jax.Array) -> jax.Array:
    """float32 (m, k) @ (k, n) as ``high`` computes it: a and b split into
    bf16 hi and lo parts, hi·hi + hi·lo + lo·hi, each product exact and
    summed in float32, the lo·lo pass left out."""
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    return mm(a_hi, b_hi) + mm(a_hi, b_lo) + mm(a_lo, b_hi)


@partial(jax.jit, donate_argnums=(0,))
def control_step(state, points: jax.Array):
    """MiniBatch K-Means step of ``bench/reference.py`` at ``high``."""
    c = state.centroids
    xn = jnp.sum(points * points, axis=1, keepdims=True)
    cn = jnp.sum(c * c, axis=1)[None, :]
    d2 = xn + cn - 2.0 * dot_3pass(points, c.T)
    labels = jnp.argmin(d2, axis=1)
    onehot = jax.nn.one_hot(labels, c.shape[0], dtype=jnp.float32)
    batch_counts = onehot.sum(axis=0)
    sums = dot_3pass(onehot.T, points)
    counts = state.counts + batch_counts
    eta = jnp.where(counts > 0, batch_counts / jnp.maximum(counts, 1.0), 0.0)
    means = sums / jnp.maximum(batch_counts, 1.0)[:, None]
    moved = (1.0 - eta)[:, None] * c + eta[:, None] * means
    return type(state)(centroids=moved, counts=counts)
