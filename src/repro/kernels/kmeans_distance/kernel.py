"""Pallas TPU kernels for the K-Means O(n·c) distance phase.

The paper's compute hot-spot is phase 1 of K-Means: Euclidean distances
between all n points and c centroids.  On TPU we express it as
``||x||^2 + ||c||^2 - 2 x c^T`` so the inner contraction runs on the MXU,
tiled so each (block_n × d) point panel and (block_c × d) centroid panel sit
in VMEM and each grid step emits one (block_n × block_c) output tile.

Two kernels:

* ``pairwise_sq_dists_pallas`` — materializes the (n, c) distance matrix.
* ``assign_pallas`` — fused distances + running argmin over centroid blocks,
  the K-Means step's path: the grid's trailing dimension walks centroid
  panels while per-lane running minima stay in VMEM scratch, so the (n, c)
  matrix is never written to HBM (the K-Means inner loop only needs the
  argmin), and a tile costs elementwise compares rather than cross-lane
  reductions.

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
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_C = 256
# the fused assignment's largest blocks (``ops.assign_blocks``); on one
# v5e at 26,000 x 8,192, 256 x 2,048 ran in 1.85 ms, 512 x 1,024 in 1.92,
# 256 x 256 in 2.28
ASSIGN_BLOCK_N = 256
ASSIGN_BLOCK_C = 2048
LANES = 128


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

def _assign_kernel(x_ref, c_ref, labels_ref, best_ref, run_min, run_idx):
    """One (block_n, block_c) tile.  ``run_min``/``run_idx`` hold, per row
    and per lane, the least distance seen so far in that lane and the
    lowest centroid index that reached it: a tile costs only elementwise
    compares and selects.  The one cross-lane reduction runs on the last
    centroid block."""
    j = pl.program_id(1)
    bc = c_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        run_min[...] = jnp.full_like(run_min, jnp.inf)
        run_idx[...] = jnp.zeros_like(run_idx)

    d2 = _dist_tile(x_ref[...], c_ref[...])                  # (bn, bc)
    lane = jax.lax.broadcasted_iota(jnp.int32, run_idx.shape, 1)
    best, idx = run_min[...], run_idx[...]
    for q in range(bc // LANES):
        # strict <: a lane keeps the lowest index among equal distances,
        # since its indices grow with q and j
        v = d2[:, q * LANES:(q + 1) * LANES]
        take = v < best
        best = jnp.where(take, v, best)
        idx = jnp.where(take, lane + (j * bc + q * LANES), idx)
    run_min[...] = best
    run_idx[...] = idx

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        row_min = jnp.min(best, axis=1, keepdims=True)
        # the lowest index among the lanes that hold the row's minimum
        cand = jnp.where(best == row_min, idx, jnp.iinfo(jnp.int32).max)
        labels_ref[...] = jnp.min(cand, axis=1, keepdims=True)
        best_ref[...] = row_min


@partial(jax.jit, static_argnames=("block_n", "block_c", "interpret"))
def assign_pallas(x: jax.Array, c: jax.Array, *,
                  block_n: int = DEFAULT_BLOCK_N,
                  block_c: int = DEFAULT_BLOCK_C,
                  interpret: bool = False):
    """Fused K-Means assignment: returns (labels (n, 1) int32, best (n, 1)
    f32), the lowest index among equal distances.  n % block_n == 0,
    k % block_c == block_c % 128 == 0 and d % 128 == 0 (``ops.py`` pads).
    The outputs are 2-D because Mosaic refuses 1-D ``(block_n,)`` output
    blocks: their XLA and Mosaic tilings differ."""
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
        scratch_shapes=[pltpu.VMEM((block_n, LANES), jnp.float32),
                        pltpu.VMEM((block_n, LANES), jnp.int32)],
        interpret=interpret,
    )(x, c)
    return labels, best
