"""MiniBatch K-Means in JAX — the paper's representative streaming workload.

K-Means has complexity O(n·c): phase 1 assigns each of n points to its
nearest of c centroids, the compute hot-spot (on TPU the ``kmeans_distance``
fused distance + argmin Pallas kernel, which never writes the (n, c)
distances to HBM; a jnp fallback elsewhere); phase 2 updates centroid
positions with the MiniBatch rule (Sculley 2010 / sklearn MiniBatchKMeans):
per-centroid counts give a decaying learning rate ``eta = m_batch / count``
so centroids converge as streams arrive.

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


def assign(points: jax.Array, centroids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Returns (labels (n,), sq_dist_to_assigned (n,)), the lowest index
    among equal distances, from the fused kernel: the (n, c) distance
    matrix is never materialised."""
    from repro.kernels.kmeans_distance import ops as kd_ops

    return kd_ops.assign(points, centroids)


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

