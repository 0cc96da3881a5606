"""Mamba-2 (SSD — state-space duality) block in JAX.  [arXiv:2405.21060]

Chunked SSD algorithm (the paper's Listing 1, ported to JAX): sequence split
into chunks of Q; within a chunk the recurrence is computed in its dual
quadratic "attention" form (MXU-friendly), across chunks a tiny recurrence
on the (H, P, N) states links them.  Decode is the pure recurrence — O(1)
in sequence length, which is what makes the ``long_500k`` cell tractable.

Block layout (Mamba-2 defaults): in-proj → causal depthwise conv(4) on
(x,B,C) → SSD → gated RMSNorm → out-proj.  Scalar A per head; ngroups=1.
A prefill (``return_state``) runs the SSD through ``kernels/ssd_scan``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.models.layers import dense_init

__all__ = ["ssm_init", "ssm_specs", "apply_ssm", "ssm_cache_init",
           "ssm_cache_specs", "ssm_decode_step", "ssd_chunked", "ssd_recurrent"]


def _dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads, cfg.ssm_state


def ssm_init(key, cfg, dtype):
    d = cfg.d_model
    di, nh, ns = _dims(cfg)
    conv_ch = di + 2 * ns                     # x, B, C all pass the conv
    ks = jax.random.split(key, 8)
    dt = jnp.exp(jax.random.uniform(ks[6], (nh,), jnp.float32)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {
        "in_z": dense_init(ks[0], (d, di), d, dtype),
        "in_x": dense_init(ks[1], (d, di), d, dtype),
        "in_B": dense_init(ks[2], (d, ns), d, dtype),
        "in_C": dense_init(ks[3], (d, ns), d, dtype),
        "in_dt": dense_init(ks[4], (d, nh), d, dtype),
        "conv_w": (jax.random.normal(ks[5], (cfg.ssm_conv, conv_ch), jnp.float32)
                   / math.sqrt(cfg.ssm_conv)).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(dt)),    # softplus^-1(dt)
        "norm_scale": jnp.ones((di,), dtype),
        "out": dense_init(ks[7], (di, d), di, dtype),
    }


def ssm_specs(cfg):
    return {"in_z": (None, "ssm_inner"), "in_x": (None, "ssm_inner"),
            "in_B": (None, None), "in_C": (None, None),
            "in_dt": (None, None), "conv_w": (None, None), "conv_b": (None,),
            "A_log": (None,), "D": (None,), "dt_bias": (None,),
            "norm_scale": ("ssm_inner",), "out": ("ssm_inner", None)}


def _causal_conv(xbc, conv_w, conv_b, conv_state=None):
    """Depthwise causal conv over time.  xbc (B, S, CH); conv_w (W, CH).
    With ``conv_state`` (B, W-1, CH) the history is prepended (decode)."""
    W = conv_w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((xbc.shape[0], W - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = conv_state.astype(xbc.dtype)
    full = jnp.concatenate([pad, xbc], axis=1)                       # (B, S+W-1, CH)
    out = sum(full[:, i:i + xbc.shape[1]] * conv_w[i][None, None]
              for i in range(W))
    return jax.nn.silu(out + conv_b[None, None]), full[:, -(W - 1):]


def _segsum(a):
    """a (..., L) -> (..., L, L) lower-tri cumulative sums: sum_{i<s<=j} a_s."""
    L = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]                       # (..., j, i)
    mask = jnp.tril(jnp.ones((L, L), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, Bm, Cm, chunk, h0=None):
    """SSD in chunked dual form.

    x  (B, S, H, P) inputs per head
    dt (B, S, H)    softplus'd step sizes
    A  (H,)         negative scalars
    Bm, Cm (B, S, N) shared across heads (ngroups=1)
    Returns y (B, S, H, P) and final state (B, H, P, N).
    """
    Bsz, S, H, Pd = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    xb = x.reshape(Bsz, nc, Q, H, Pd)
    dtb = dt.reshape(Bsz, nc, Q, H)
    Bb = Bm.reshape(Bsz, nc, Q, N)
    Cb = Cm.reshape(Bsz, nc, Q, N)
    a = dtb * A[None, None, None]                                    # (B,nc,Q,H) ≤ 0
    a = jnp.moveaxis(a, -1, 1)                                       # (B,H,nc,Q)
    a_cum = jnp.cumsum(a, axis=-1)
    L = jnp.exp(_segsum(a))                                          # (B,H,nc,Q,Q)
    xdt = xb * dtb[..., None]                                        # dt-weighted input
    # intra-chunk (dual quadratic form)
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cb, Bb, L, xdt)
    # chunk-final states
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)                  # (B,H,nc,Q)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bb, decay_states, xdt)
    if h0 is not None:
        states = jnp.concatenate([h0[:, None], states], axis=1)      # (B,nc+1,H,P,N)
    else:
        states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    # inter-chunk recurrence (over nc+1 states)
    chunk_decay = a_cum[..., -1]                                     # (B,H,nc)
    pad = jnp.pad(chunk_decay, ((0, 0), (0, 0), (1, 0)))
    dec = jnp.exp(_segsum(pad))                                      # (B,H,nc+1,nc+1)
    dec = jnp.where(jnp.isfinite(dec), dec, 0.0)
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", dec, states)        # (B,nc+1,H,P,N)
    prev_states, final_state = new_states[:, :-1], new_states[:, -1]
    # inter-chunk contribution to outputs
    state_decay = jnp.exp(a_cum)                                     # (B,H,nc,Q)
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cb, prev_states, state_decay)
    y = (y_diag + y_off).reshape(Bsz, S, H, Pd)
    return y, final_state


def ssd_recurrent(x, dt, A, Bm, Cm, h0):
    """Single-step recurrence (decode).  x (B,1,H,P) ... h0 (B,H,P,N)."""
    a = jnp.exp(dt[:, 0] * A[None])                                  # (B,H)
    xdt = x[:, 0] * dt[:, 0, :, None]                                # (B,H,P)
    h = a[..., None, None] * h0 + jnp.einsum("bhp,bn->bhpn", xdt, Bm[:, 0])
    y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0], h)
    return y[:, None], h


def _gated_norm(y, z, scale, eps):
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(yf * yf, axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32))


def _proj_all(p, cfg, x):
    di, nh, ns = _dims(cfg)
    z = x @ p["in_z"]
    xi = x @ p["in_x"]
    Bm = x @ p["in_B"]
    Cm = x @ p["in_C"]
    dt = jax.nn.softplus((x @ p["in_dt"]).astype(jnp.float32)
                         + p["dt_bias"][None, None])
    return z, xi, Bm, Cm, dt


def apply_ssm(p, cfg, x, h0=None, conv_state=None, return_state=False):
    """Full-sequence Mamba-2 block.  x (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    di, nh, ns = _dims(cfg)
    z, xi, Bm, Cm, dt = _proj_all(p, cfg, x)
    xbc = jnp.concatenate([xi, Bm, Cm], axis=-1)
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xi, Bm, Cm = jnp.split(xbc, [di, di + ns], axis=-1)
    xi = constrain(xi, ("batch", None, "ssm_inner"))
    A = -jnp.exp(p["A_log"])
    xh = xi.astype(jnp.float32).reshape(B, S, nh, cfg.ssm_head_dim)
    Bm, Cm = Bm.astype(jnp.float32), Cm.astype(jnp.float32)
    if return_state and h0 is None:
        # prefill: the SSD kernel (Pallas on TPU, ssd_chunked elsewhere);
        # training keeps ssd_chunked, which has a gradient
        y, hT = ssd_scan(xh, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
    else:
        y, hT = ssd_chunked(xh, dt, A, Bm, Cm, cfg.ssm_chunk, h0)
    y = y + xh * p["D"][None, None, :, None]
    y = y.reshape(B, S, di)
    out = _gated_norm(y, z, p["norm_scale"], cfg.norm_eps).astype(x.dtype)
    out = out @ p["out"]
    if return_state:
        return out, (hT, new_conv)
    return out


# -- decode ------------------------------------------------------------------

def ssm_cache_init(cfg, batch, dtype=jnp.float32):
    di, nh, ns = _dims(cfg)
    conv_ch = di + 2 * ns
    return {"h": jnp.zeros((batch, nh, cfg.ssm_head_dim, ns), jnp.float32),
            "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), dtype)}


def ssm_cache_specs(cfg):
    return {"h": ("batch", None, None, None), "conv": ("batch", None, None)}


def ssm_decode_step(p, cfg, x, cache):
    """x (B, 1, d); cache {h, conv} -> (out (B,1,d), new cache)."""
    B = x.shape[0]
    di, nh, ns = _dims(cfg)
    z, xi, Bm, Cm, dt = _proj_all(p, cfg, x)
    xbc = jnp.concatenate([xi, Bm, Cm], axis=-1)
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], cache["conv"])
    xi, Bm, Cm = jnp.split(xbc, [di, di + ns], axis=-1)
    A = -jnp.exp(p["A_log"])
    xh = xi.astype(jnp.float32).reshape(B, 1, nh, cfg.ssm_head_dim)
    y, h = ssd_recurrent(xh, dt, A, Bm.astype(jnp.float32),
                         Cm.astype(jnp.float32), cache["h"])
    y = y + xh * p["D"][None, None, :, None]
    y = y.reshape(B, 1, di)
    out = _gated_norm(y, z, p["norm_scale"], cfg.norm_eps).astype(x.dtype)
    return out @ p["out"], {"h": h, "conv": new_conv.astype(cache["conv"].dtype)}
