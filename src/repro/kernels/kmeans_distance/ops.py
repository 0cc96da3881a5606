"""Jitted public wrappers for the K-Means distance kernels.

Dispatch policy: on TPU the Pallas kernels run compiled; everywhere else the
pure-jnp reference executes (XLA fuses it fine on CPU, and the dry-run's
CPU-hosted compile must not contain TPU-Pallas custom calls).  Tests force
the Pallas path with ``interpret=True``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.kmeans_distance import kernel as _k
from repro.kernels.kmeans_distance.ref import assign_ref, pairwise_sq_dists_ref

__all__ = ["pairwise_sq_dists", "assign", "assign_blocks", "pad_to_multiple",
           "packs", "split_bf16", "pack"]

# bf16 pieces that hold a float32 exactly (3 x 8 significant bits)
PIECES = 3
# what padded centroids hold in every coordinate: argmin never takes them
SENTINEL = 1e17


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


def packs(d: int) -> bool:
    """Whether d coordinates take the packed single-pass layout: the
    PIECES x PIECES piece products of each side by side in one pass of
    the MXU's 128 lanes (d <= 14)."""
    return PIECES * PIECES * d <= _k.LANES


def split_bf16(a: jax.Array) -> list[jax.Array]:
    """Three bf16 pieces whose sum is the float32 ``a`` exactly: each keeps
    the top 8 significant bits of what the pieces before it left.  Masking
    the bits, not rounding, leaves no convert for the compiler to fold."""
    pieces, rest = [], a.astype(jnp.float32)
    for _ in range(PIECES):
        bits = jax.lax.bitcast_convert_type(rest, jnp.uint32)
        head = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
        pieces.append(head.astype(jnp.bfloat16))
        rest = rest - head
    return pieces


def _spread(a: jax.Array) -> jax.Array:
    """(m, d) -> (m, 128) float32: lane b·d + k holds a[:, k] for each of
    the 9 products b, zeros beyond.  A matmul against 0/1 places the
    coordinates in one fused pass over the points' own layout; each lane
    sums one term times 1, exact at ``HIGHEST``."""
    d = a.shape[1]
    lanes = np.arange(PIECES * PIECES * d)
    place = np.zeros((d, _k.LANES), np.float32)
    place[lanes % d, lanes] = 1.0
    return jnp.dot(a, jnp.asarray(place), precision=jax.lax.Precision.HIGHEST)


def _select(pieces: list[jax.Array], which: np.ndarray) -> jax.Array:
    """Lane l of ``pieces[which[l]]``."""
    return jnp.where(which == 0, pieces[0],
                     jnp.where(which == 1, pieces[1], pieces[2]))


def pack(x: jax.Array, c: jax.Array):
    """The operands of one bf16 MXU pass for ``packs(d)``: points (n, 128)
    as [x3 x3 x3 x2 x2 x2 x1 x1 x1], centroids (k, 128) as -2 [c3 c2 c1 c3
    c2 c1 c3 c2 c1] (a power of two: still exact), so the contraction sums
    -2 xi.cj over all nine piece pairs, the smallest first; and the
    float32 norms (n, 1) and (1, k)."""
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    block = np.arange(_k.LANES) // x.shape[1]      # 9 and above: zeros
    xp = _select(split_bf16(_spread(x)), 2 - block // 3)
    cp = _select(split_bf16(_spread(-2.0 * c)), 2 - block % 3)
    xn = jnp.sum(x * x, axis=-1, keepdims=True)
    cn = jnp.sum(c * c, axis=-1, keepdims=True).T
    return xp, cp, xn, cn


def _operands(x: jax.Array, c: jax.Array, bn: int, bc: int):
    """The kernels' operands: rows padded to the blocks (centroid rows with
    ``SENTINEL`` coordinates), then ``pack``ed, or for wider d zero-padded
    to 128 lanes with no norms."""
    xp = pad_to_multiple(x, 0, bn)
    cp = pad_to_multiple(c, 0, bc)
    if cp.shape[0] != c.shape[0]:
        cp = cp.at[c.shape[0]:].set(SENTINEL)
    if packs(x.shape[1]):
        return pack(xp, cp)
    return pad_to_multiple(xp, 1, _k.LANES), pad_to_multiple(cp, 1, _k.LANES)


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
    out = _k.pairwise_sq_dists_pallas(*_operands(x, c, bn, bc),
                                      block_n=bn, block_c=bc,
                                      interpret=interpret)
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
    labels, best = _k.assign_pallas(*_operands(x, c, bn, bc),
                                    block_n=bn, block_c=bc,
                                    interpret=interpret)
    return labels[:n, 0], best[:n, 0]
