"""Pallas TPU kernel for the K-Means O(n·c) distance phase.

The paper's compute hot-spot is phase 1 of K-Means: Euclidean distances
between all n points and c centroids.  On TPU we express it as
``||x||^2 + ||c||^2 - 2 x c^T`` so the inner contraction runs on the MXU,
tiled so each (block_n × K) point panel and (block_c × K) centroid panel sit
in VMEM.

``assign_pallas`` fuses the distances with a running argmin over centroid
blocks: the grid's trailing dimension walks centroid panels while per-lane
running minima stay in VMEM scratch, so the (n, c) matrix is never written
to HBM (the K-Means inner loop only needs the argmin), and a tile costs
elementwise compares rather than cross-lane reductions.

The contraction runs as one bf16 MXU pass for every d: ``ops.pack`` splits
each float32 coordinate exactly into three bf16 pieces and lays the nine
piece products of every coordinate side by side along
K = ``ops.packed_lanes(d)`` = ceil(9·d / 128)·128 lanes, the factor -2
folded into the centroid side, and hands the kernel the float32 norms.
Each bf16 product is exact in float32, so one pass with float32
accumulation sums the same products as ``Precision.HIGHEST``'s six, and
more of them; zero lanes contribute 0 to every dot.

Grid iteration on TPU is sequential over the trailing axis, which the
kernel relies on for its running-min accumulation (standard TPU Pallas
revisiting semantics).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the largest blocks (``ops.assign_blocks``), for 128 packed lanes; on one
# v5e at 26,000 x 8,192 x 9, packed: 256 x 2,048 ran in 0.421 ms, 512 x
# 2,048 in 0.456, 1,024 x 1,024 in 0.500, and with the whole centroid
# table held in VMEM 0.421
ASSIGN_BLOCK_N = 256
ASSIGN_BLOCK_C = 2048
LANES = 128


def _dist_tile(x_blk, c_blk, xn, cn):
    """(bn, K), (bc, K) bf16 panels in ``ops.pack``'s layout, whose
    contraction is -2 x.c, and the norms (bn, 1), (1, bc) -> (bn, bc)
    squared distances."""
    # bf16 x bf16 products are exact in f32: one pass at default precision
    dot = jax.lax.dot_general(x_blk, c_blk, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return jnp.maximum(xn + cn + dot, 0.0)


def _assign_kernel(x_ref, c_ref, xn_ref, cn_ref, labels_ref, best_ref,
                   run_min, run_idx):
    """One (block_n, block_c) tile.  ``run_min``/``run_idx`` hold, per row
    and per lane, the least distance seen so far in that lane and the
    lowest 128-centroid group that reached it (centroid = group * 128 +
    lane): a tile costs only elementwise compares and selects.  The one
    cross-lane reduction runs on the last centroid block."""
    j = pl.program_id(1)
    groups = c_ref.shape[0] // LANES

    @pl.when(j == 0)
    def _init():
        run_min[...] = jnp.full_like(run_min, jnp.inf)
        run_idx[...] = jnp.zeros_like(run_idx)

    d2 = _dist_tile(x_ref[...], c_ref[...], xn_ref[...], cn_ref[...])
    best, idx = run_min[...], run_idx[...]
    for q in range(groups):
        # strict <: a lane keeps the lowest index among equal distances,
        # since its groups grow with q and j
        v = d2[:, q * LANES:(q + 1) * LANES]
        take = v < best
        best = jnp.where(take, v, best)
        idx = jnp.where(take, j * groups + q, idx)
    run_min[...] = best
    run_idx[...] = idx

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        lane = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 1)
        row_min = jnp.min(best, axis=1, keepdims=True)
        # the lowest index among the lanes that hold the row's minimum
        cand = jnp.where(best == row_min, idx * LANES + lane,
                         jnp.iinfo(jnp.int32).max)
        labels_ref[...] = jnp.min(cand, axis=1, keepdims=True)
        best_ref[...] = row_min


@partial(jax.jit, static_argnames=("block_n", "block_c", "interpret"))
def assign_pallas(x: jax.Array, c: jax.Array, xn: jax.Array, cn: jax.Array,
                  *, block_n: int, block_c: int, interpret: bool = False):
    """Fused K-Means assignment over ``ops.pack``'s operands: points x
    (n, K) and centroids c (k, K) in bf16, norms xn (n, 1) and cn (1, k).
    Returns (labels (n, 1) int32, best (n, 1) f32), the lowest index among
    equal distances.  n % block_n == 0, k % block_c == block_c % 128 == 0
    and K % 128 == 0.
    The outputs are 2-D because Mosaic refuses 1-D ``(block_n,)`` output
    blocks: their XLA and Mosaic tilings differ."""
    n, lanes = x.shape
    k, _ = c.shape
    grid = (n // block_n, k // block_c)   # trailing axis: centroid panels
    labels, best = pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, lanes), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, lanes), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
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
    )(x, c, xn, cn)
    return labels, best
