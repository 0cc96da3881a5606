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

For d <= 14 (``ops.packs``) the contraction runs as one bf16 MXU pass:
``ops.pack`` splits each float32 coordinate exactly into three bf16 pieces
and lays the nine piece products of every coordinate side by side along
the 128 lanes (9·d <= 128), the factor -2 folded into the centroid side,
and hands the kernel the float32 norms.  Each bf16 product is exact in
float32, so one pass with float32 accumulation sums the same products as
``Precision.HIGHEST``'s six, and more of them.  Wider d is zero-padded to
the 128-lane boundary and contracted in float32 at ``HIGHEST``, norms
computed in the kernel; zero padding contributes 0 to every norm and dot.

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
# v5e at 26,000 x 8,192 x 9, packed: 256 x 2,048 ran in 0.421 ms, 512 x
# 2,048 in 0.456, 1,024 x 1,024 in 0.500, and with the whole centroid
# table held in VMEM 0.421 (at HIGHEST: 256 x 2,048 in 1.82 ms,
# 512 x 1,024 in 1.92, 256 x 256 in 2.28)
ASSIGN_BLOCK_N = 256
ASSIGN_BLOCK_C = 2048
LANES = 128


def _dist_tile(x_blk, c_blk, xn=None, cn=None):
    """(bn, K), (bc, K) -> (bn, bc) squared distances; fp32 accumulation.
    Given the norms ``xn`` (bn, 1) and ``cn`` (1, bc), the panels are
    ``ops.pack``'s bf16 layout, whose contraction is -2 x.c."""
    if xn is not None:
        # bf16 x bf16 products are exact in f32: one pass at default precision
        dot = jax.lax.dot_general(x_blk, c_blk, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return jnp.maximum(xn + cn + dot, 0.0)
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


def _in_specs(block_n, block_c, k_lanes, norms):
    """Point and centroid panels, and with ``norms`` their norms."""
    specs = [pl.BlockSpec((block_n, k_lanes), lambda i, j: (i, 0)),
             pl.BlockSpec((block_c, k_lanes), lambda i, j: (j, 0))]
    if norms:
        specs += [pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, block_c), lambda i, j: (0, j))]
    return specs


# --------------------------------------------------------------------------
# Kernel 1: full (n, c) distance matrix
# --------------------------------------------------------------------------

def _dists_kernel(*refs):
    *in_refs, out_ref = refs
    out_ref[...] = _dist_tile(*(r[...] for r in in_refs)).astype(out_ref.dtype)


@partial(jax.jit, static_argnames=("block_n", "block_c", "interpret"))
def pairwise_sq_dists_pallas(x: jax.Array, c: jax.Array,
                             xn: jax.Array | None = None,
                             cn: jax.Array | None = None, *,
                             block_n: int = DEFAULT_BLOCK_N,
                             block_c: int = DEFAULT_BLOCK_C,
                             interpret: bool = False) -> jax.Array:
    """x (n, K), c (k, K) -> (n, k) float32.  n % block_n == k % block_c == 0
    and K % 128 == 0.  Either ``ops.pack``'s operands with their norms xn
    (n, 1) and cn (1, k), or float32 coordinates zero-padded to K."""
    n, d = x.shape
    k, _ = c.shape
    norms = () if xn is None else (xn, cn)
    grid = (n // block_n, k // block_c)
    return pl.pallas_call(
        _dists_kernel,
        grid=grid,
        in_specs=_in_specs(block_n, block_c, d, bool(norms)),
        out_specs=pl.BlockSpec((block_n, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(x, c, *norms)


# --------------------------------------------------------------------------
# Kernel 2: fused assignment (distances + running argmin, no HBM matrix)
# --------------------------------------------------------------------------

def _assign_kernel(*refs):
    """One (block_n, block_c) tile.  ``run_min``/``run_idx`` hold, per row
    and per lane, the least distance seen so far in that lane and the
    lowest 128-centroid group that reached it (centroid = group * 128 +
    lane): a tile costs only elementwise compares and selects.  The one
    cross-lane reduction runs on the last centroid block."""
    *in_refs, labels_ref, best_ref, run_min, run_idx = refs
    j = pl.program_id(1)
    groups = in_refs[1].shape[0] // LANES

    @pl.when(j == 0)
    def _init():
        run_min[...] = jnp.full_like(run_min, jnp.inf)
        run_idx[...] = jnp.zeros_like(run_idx)

    d2 = _dist_tile(*(r[...] for r in in_refs))             # (bn, bc)
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
def assign_pallas(x: jax.Array, c: jax.Array,
                  xn: jax.Array | None = None, cn: jax.Array | None = None, *,
                  block_n: int = DEFAULT_BLOCK_N,
                  block_c: int = DEFAULT_BLOCK_C,
                  interpret: bool = False):
    """Fused K-Means assignment: returns (labels (n, 1) int32, best (n, 1)
    f32), the lowest index among equal distances.  n % block_n == 0,
    k % block_c == block_c % 128 == 0 and K % 128 == 0; operands as
    ``pairwise_sq_dists_pallas`` takes them.
    The outputs are 2-D because Mosaic refuses 1-D ``(block_n,)`` output
    blocks: their XLA and Mosaic tilings differ."""
    n, d = x.shape
    k, _ = c.shape
    norms = () if xn is None else (xn, cn)
    grid = (n // block_n, k // block_c)   # trailing axis: centroid panels
    labels, best = pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=_in_specs(block_n, block_c, d, bool(norms)),
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
    )(x, c, *norms)
    return labels, best
