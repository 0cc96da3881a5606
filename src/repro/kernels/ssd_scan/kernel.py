"""Pallas TPU kernel for Mamba-2 SSD (state-space duality).  [arXiv:2405.21060]

TPU adaptation of the chunked SSD algorithm: the sequence is cut into
chunks of Q; within a chunk the recurrence is evaluated in its *dual
quadratic form* (two (Q,N)·(N,Q) / (Q,Q)·(Q,P) matmuls — MXU work), and the
(P, N) inter-chunk state is carried in VMEM scratch across the sequential
trailing grid axis.  grid = (B·H, S/Q); one head-chunk tile per step:

    y_chunk = (C Bᵀ ⊙ L) (dt·x)  +  (C hᵀ-decay)        # intra + carry-in
    h      ← exp(Σa) h + Σ_s exp(Σa − cum_s) dt·x_s ⊗ B_s

(L = exp(segsum(a)) lower-triangular decay matrix.)  The final state is
emitted for decode hand-off.  B/C are shared across heads (ngroups=1), so
their BlockSpecs divide the head index out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _column(row):
    """(1, Q) -> (Q, 1): the diagonal of the row's broadcast, summed out
    (Mosaic moves a vector from lanes to sublanes this way)."""
    Q = row.shape[1]
    eye = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) == \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (Q, Q)), 0.0),
                   axis=1, keepdims=True)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr, *,
                n_chunks):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)            # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)          # (1, Q)
    a = a_ref[0].astype(jnp.float32)            # (1, Q)  = dt * A  (≤ 0)
    Bm = b_ref[0].astype(jnp.float32)           # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)           # (Q, N)
    Q = x.shape[0]

    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = row >= col
    # inclusive cumulative sums of a, as a column (Q, 1) and a row (1, Q)
    cum = jnp.sum(jnp.where(tri, jnp.broadcast_to(a, (Q, Q)), 0.0),
                  axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(row <= col,
                                jnp.broadcast_to(_column(a), (Q, Q)), 0.0),
                      axis=0, keepdims=True)
    total = jnp.sum(a, axis=1, keepdims=True)   # (1, 1) = cum at the end
    # L[i, j] = exp(cum_i - cum_j) for j <= i (decay from step j+1..i)
    L = jnp.where(tri, jnp.exp(cum - cum_row), 0.0)

    xdt = x * _column(dt)                       # (Q, P)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    y_intra = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (Q,P)

    h = h_scr[...]                              # (P, N)
    y_carry = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32) * jnp.exp(cum)
    y_ref[0] = (y_intra + y_carry).astype(y_ref.dtype)

    # state update: h' = exp(cum_Q) h + Σ_s exp(cum_Q - cum_s) xdt_s ⊗ B_s
    w = jnp.exp(total - cum)                    # (Q, 1)
    dh = jax.lax.dot_general(xdt * w, Bm, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)       # (P, N)
    h_scr[...] = jnp.exp(total) * h + dh

    @pl.when(c_idx == n_chunks - 1)
    def _emit_state():
        hout_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, a, Bm, Cm, *, chunk: int = DEFAULT_CHUNK,
                    interpret: bool = False):
    """x (BH, S, P); dt, a (BH, 1, S); Bm, Cm (Bg, S, N) with BH = Bg·H.
    Returns (y (BH, S, P) fp32, h_final (BH, P, N) fp32).  S % chunk == 0.
    dt and a are rows (a block's last two dimensions must be whole or
    tile-aligned: (1, Q) of a (1, S) row is)."""
    BH, S, P = x.shape
    Bg = Bm.shape[0]
    H = BH // Bg
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    kernel = functools.partial(_ssd_kernel, n_chunks=nc)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, 1, Q), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, Q, N), lambda b, c, H=H: (b // H, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c, H=H: (b // H, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, P, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, a, Bm, Cm)
