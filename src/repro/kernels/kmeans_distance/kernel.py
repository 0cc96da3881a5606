"""Pallas TPU kernels for the K-Means O(n·c) distance phase.

The paper's compute hot-spot is phase 1 of K-Means: Euclidean distances
between all n points and c centroids.  On TPU we express it as
``||x||^2 + ||c||^2 - 2 x c^T`` so the inner contraction runs on the MXU,
tiled so each (block_n × d) point panel and (block_c × d) centroid panel sit
in VMEM and each grid step emits one (block_n × block_c) output tile.

Two kernels:

* ``pairwise_sq_dists_pallas`` — materializes the (n, c) distance matrix.
* ``assign_pallas`` — fused distances + running argmin over centroid blocks:
  the grid's trailing dimension walks centroid panels while the output
  (labels, best) block stays resident in VMEM, so the (n, c) matrix is never
  written to HBM — an O(c/d)× HBM-write saving over kernel 1 for the
  assignment use-case (the K-Means inner loop only needs argmin).

Feature dim d is zero-padded to the 128-lane boundary by ``ops.py``;
zero padding does not change distances (contributes 0 to every norm/dot).
Grid iteration on TPU is sequential over the trailing axis, which the fused
kernel relies on for its running-min accumulation (standard TPU Pallas
revisiting semantics).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_C = 256


def _dist_tile(x_blk, c_blk):
    """(bn, d), (bc, d) -> (bn, bc) squared distances; fp32 accumulation."""
    x32 = x_blk.astype(jnp.float32)
    c32 = c_blk.astype(jnp.float32)
    xn = jnp.sum(x32 * x32, axis=-1, keepdims=True)          # (bn, 1)
    cn = jnp.sum(c32 * c32, axis=-1, keepdims=True).T        # (1, bc)
    # full f32 contraction: ||x||^2 + ||c||^2 - 2 x.c cancels, and the
    # chip's default f32 matmul precision (bf16 passes) would swamp it
    dot = jax.lax.dot_general(x32, c32, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    return jnp.maximum(xn + cn - 2.0 * dot, 0.0)


# --------------------------------------------------------------------------
# Kernel 1: full (n, c) distance matrix
# --------------------------------------------------------------------------

def _dists_kernel(x_ref, c_ref, out_ref):
    out_ref[...] = _dist_tile(x_ref[...], c_ref[...]).astype(out_ref.dtype)


@partial(jax.jit, static_argnames=("block_n", "block_c", "interpret"))
def pairwise_sq_dists_pallas(x: jax.Array, c: jax.Array, *,
                             block_n: int = DEFAULT_BLOCK_N,
                             block_c: int = DEFAULT_BLOCK_C,
                             interpret: bool = False) -> jax.Array:
    """x (n, d), c (k, d) -> (n, k) float32.  n % block_n == k % block_c == 0
    and d % 128 == 0 (``ops.py`` pads)."""
    n, d = x.shape
    k, _ = c.shape
    grid = (n // block_n, k // block_c)
    return pl.pallas_call(
        _dists_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(x, c)


# --------------------------------------------------------------------------
# Kernel 2: fused assignment (distances + running argmin, no HBM matrix)
# --------------------------------------------------------------------------

def _assign_kernel(x_ref, c_ref, labels_ref, best_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, jnp.inf)
        labels_ref[...] = jnp.zeros_like(labels_ref)

    d2 = _dist_tile(x_ref[...], c_ref[...])                  # (bn, bc)
    blk_best = jnp.min(d2, axis=1, keepdims=True)            # (bn, 1)
    blk_arg = jnp.argmin(d2, axis=1, keepdims=True).astype(jnp.int32)
    bc = d2.shape[1]
    cur_best = best_ref[...]
    take = blk_best < cur_best
    best_ref[...] = jnp.where(take, blk_best, cur_best)
    labels_ref[...] = jnp.where(take, blk_arg + j * bc, labels_ref[...])


@partial(jax.jit, static_argnames=("block_n", "block_c", "interpret"))
def assign_pallas(x: jax.Array, c: jax.Array, *,
                  block_n: int = DEFAULT_BLOCK_N,
                  block_c: int = DEFAULT_BLOCK_C,
                  interpret: bool = False):
    """Fused K-Means assignment: returns (labels (n, 1) int32, best (n, 1)
    f32).  The outputs are 2-D because Mosaic refuses 1-D ``(block_n,)``
    output blocks: their XLA and Mosaic tilings differ."""
    n, d = x.shape
    k, _ = c.shape
    grid = (n // block_n, k // block_c)   # trailing axis: centroid panels
    labels, best = pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, c)
    return labels, best
