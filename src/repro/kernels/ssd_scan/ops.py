"""Jitted wrapper for the SSD kernel: layout adaptation + dispatch.

Model-side layout is (B, S, H, P) with per-head dt and shared B/C
(``models.ssm``); the kernel wants head-major (B·H, S, P).  Fallback is the
chunked pure-JAX SSD in ``models.ssm`` (same math, XLA-fused), oracle is the
naive recurrence in ``ref.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan import kernel as _k
from repro.kernels.ssd_scan.ref import ssd_ref

__all__ = ["ssd_scan"]


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = _k.DEFAULT_CHUNK,
             use_pallas: bool | None = None, interpret: bool = False):
    """x (B, S, H, P); dt (B, S, H); A (H,); Bm, Cm (B, S, N).
    Returns (y (B, S, H, P) fp32, h_final (B, H, P, N) fp32)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        from repro.models.ssm import ssd_chunked
        return ssd_chunked(x, dt, A, Bm, Cm, chunk)
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    xb = jnp.moveaxis(x, 2, 1).reshape(B * H, S, P)
    dtb = jnp.moveaxis(dt, 2, 1).reshape(B * H, 1, S)
    ab = dtb * jnp.tile(A, B)[:, None, None]                 # (BH, 1, S) = dt*A
    y, h = _k.ssd_scan_pallas(xb, dtb, ab, Bm, Cm, chunk=min(chunk, S),
                              interpret=interpret)
    y = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)
    return y, h.reshape(B, H, P, N)
