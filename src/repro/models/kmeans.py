"""MiniBatch K-Means in JAX — the paper's representative streaming workload.

K-Means has complexity O(n·c): phase 1 computes Euclidean distances between
all n points and c centroids (the compute hot-spot, implemented as the
``kmeans_distance`` Pallas kernel on TPU with a jnp fallback elsewhere);
phase 2 updates centroid positions with the MiniBatch rule (Sculley 2010 /
sklearn MiniBatchKMeans): per-centroid counts give a decaying learning rate
``eta = m_batch / count`` so centroids converge as streams arrive.

The model state (centroids, counts) is what the paper shares across tasks
via file storage (S3 / Lustre) — see ``core.miniapp`` for how the sharing
policy maps to backend mechanisms.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["KMeansState", "init_state", "assign", "minibatch_step", "inertia"]


class KMeansState(NamedTuple):
    centroids: jax.Array   # (c, d)
    counts: jax.Array      # (c,) — per-centroid cumulative assignment counts


def init_state(key: jax.Array, n_centroids: int, dim: int, scale: float = 1.0) -> KMeansState:
    centroids = scale * jax.random.normal(key, (n_centroids, dim), dtype=jnp.float32)
    return KMeansState(centroids=centroids, counts=jnp.zeros((n_centroids,), jnp.float32))


def _pairwise_sq_dists(points: jax.Array, centroids: jax.Array) -> jax.Array:
    """(n, c) squared Euclidean distances via the matmul formulation
    ||x||^2 + ||c||^2 - 2 x.c^T — the MXU-friendly form the Pallas kernel tiles."""
    from repro.kernels.kmeans_distance import ops as kd_ops

    return kd_ops.pairwise_sq_dists(points, centroids)


def assign(points: jax.Array, centroids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Returns (labels (n,), sq_dist_to_assigned (n,))."""
    d2 = _pairwise_sq_dists(points, centroids)
    labels = jnp.argmin(d2, axis=1)
    best = jnp.min(d2, axis=1)
    return labels, best


@partial(jax.jit, donate_argnums=(0,))
def minibatch_step(state: KMeansState, points: jax.Array) -> KMeansState:
    """One MiniBatch K-Means update on a batch of points (n, d)."""
    labels, _ = assign(points, state.centroids)
    c = state.centroids.shape[0]
    onehot = jax.nn.one_hot(labels, c, dtype=points.dtype)          # (n, c)
    batch_counts = onehot.sum(axis=0)                               # (c,)
    # full f32: at the chip's default precision the points would be
    # rounded to bf16 on the way through the MXU
    batch_sums = jnp.matmul(onehot.T, points,
                            precision=jax.lax.Precision.HIGHEST)    # (c, d)
    new_counts = state.counts + batch_counts
    # decaying per-centroid rate; centroids with no assignments unchanged
    eta = jnp.where(new_counts > 0, batch_counts / jnp.maximum(new_counts, 1.0), 0.0)
    batch_means = batch_sums / jnp.maximum(batch_counts, 1.0)[:, None]
    new_centroids = (1.0 - eta)[:, None] * state.centroids + eta[:, None] * batch_means
    return KMeansState(centroids=new_centroids, counts=new_counts)


@jax.jit
def inertia(points: jax.Array, centroids: jax.Array) -> jax.Array:
    """Mean squared distance to the assigned centroid (clustering quality)."""
    _, best = assign(points, centroids)
    return jnp.mean(best)


def flops_estimate(n: int, c: int, d: int) -> float:
    """Analytic FLOPs of one minibatch step (distance phase dominates: 3ncd)."""
    distance = 3.0 * n * c * d
    update = 2.0 * n * c + 2.0 * n * d + 6.0 * c * d
    return distance + update
