"""Jitted public wrappers for the K-Means distance kernels.

Dispatch policy: on TPU the Pallas kernels run compiled; everywhere else the
pure-jnp reference executes (XLA fuses it fine on CPU, and the dry-run's
CPU-hosted compile must not contain TPU-Pallas custom calls).  Tests force
the Pallas path with ``interpret=True``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.kmeans_distance import kernel as _k
from repro.kernels.kmeans_distance.ref import assign_ref, pairwise_sq_dists_ref

__all__ = ["pairwise_sq_dists", "assign", "assign_blocks", "pad_to_multiple"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pad_to_multiple(a: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = a.shape[axis]
    rem = size % multiple
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, multiple - rem)
    return jnp.pad(a, pad)


def pairwise_sq_dists(x: jax.Array, c: jax.Array, *, use_pallas: bool | None = None,
                      interpret: bool = False) -> jax.Array:
    """(n, d), (k, d) -> (n, k) squared Euclidean distances."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return pairwise_sq_dists_ref(x, c)
    n, k = x.shape[0], c.shape[0]
    bn = min(_k.DEFAULT_BLOCK_N, max(8, n))
    bc = min(_k.DEFAULT_BLOCK_C, max(8, k))
    xp = pad_to_multiple(pad_to_multiple(x, 1, 128), 0, bn)
    cp = pad_to_multiple(pad_to_multiple(c, 1, 128), 0, bc)
    out = _k.pairwise_sq_dists_pallas(xp, cp, block_n=bn, block_c=bc,
                                      interpret=interpret)
    # padded centroids have ||c||=0 -> distance ||x||^2; slicing removes them
    return out[:n, :k]


def _blocks(size: int, most: int, multiple: int) -> int:
    """The block that covers ``size`` in the fewest blocks of at most
    ``most``, rounded up to ``multiple``: padding stays under one
    ``multiple`` per block."""
    size = -(-size // multiple) * multiple
    n_blocks = -(-size // most)
    return -(-size // (n_blocks * multiple)) * multiple


def assign_blocks(n: int, k: int) -> tuple[int, int]:
    """(block_n, block_c) of the fused assignment for n points and k
    centroids: wide centroid panels, so a tile's MXU work outweighs the
    grid step's fixed cost."""
    return (_blocks(n, _k.ASSIGN_BLOCK_N, 8),
            _blocks(k, _k.ASSIGN_BLOCK_C, _k.LANES))


def assign(x: jax.Array, c: jax.Array, *, use_pallas: bool | None = None,
           interpret: bool = False):
    """Fused assignment -> (labels (n,) int32, best_sq_dist (n,) f32):
    the argmin, lowest index first, of the distances
    ``pairwise_sq_dists`` gives, without the (n, k) matrix."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return assign_ref(x, c)
    n, k = x.shape[0], c.shape[0]
    bn, bc = assign_blocks(n, k)
    xp = pad_to_multiple(pad_to_multiple(x, 1, 128), 0, bn)
    cp = pad_to_multiple(pad_to_multiple(c, 1, 128), 0, bc)
    if cp.shape[0] != k:
        # padded centroids are at the origin; push them to +inf distance by
        # giving them a huge coordinate so argmin never selects padding
        pad_rows = cp.shape[0] - k
        sentinel = jnp.full((pad_rows, cp.shape[1]), 1e17, cp.dtype)
        cp = jnp.concatenate([cp[:k], sentinel], axis=0)
    labels, best = _k.assign_pallas(xp, cp, block_n=bn, block_c=bc,
                                    interpret=interpret)
    return labels[:n, 0], best[:n, 0]
