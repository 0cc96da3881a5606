"""The benchmark's own plain reference of Granite 4.0-H (granitemoehybrid),
independent of the program: float32 ``jax.numpy`` at ``highest`` matrix
precision, no kernels, cache, batching or capacity, on weights in the
published layout (Hugging Face names; an ``nn.Linear`` weight is
(out, in)), as ``bench/apps/granite_hybrid.py`` makes them from the seed.

Per layer: ``x + r * mixer(rmsnorm(x))``, then ``x + r * (moe(h) +
shared(h))`` with ``h = rmsnorm(x)``, ``r`` the residual multiplier.
Mixers: Mamba-2 (in_proj -> causal depthwise conv + bias + SiLU over x, B,
C -> the state recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t =
h_t C_t, one step at a time -> D skip -> RMSNorm of y * SiLU(z) ->
out_proj) or causal GQA with no positional encoding, scores scaled by the
attention multiplier.  MoE: softmax over the top k of the router's
logits, SwiGLU experts (``input_linear`` = [gate; up]).  Embeddings times
the embedding multiplier, tied, logits divided by the logits scaling.

Departure from the published model, as in the program: only the experts
held on this chip (``num_local_experts`` of them from ``expert_offset``;
the router scores all ``router_experts``) contribute.

Given the expert choices a program made at the positions it is judged at,
the reference routes those positions the same way and reports how far
each choice was from its own top k (``route_gap``): a bf16 program and a
float32 reference break some near ties differently, and with greedy
tokens repeating, one such tie can move most of a request's compared
logits.

It runs one layer at a time: each layer's bf16 weights are made (or
fetched) when the layer runs and upcast to float32 there, which is exact,
so the whole forward over a prompt and its answer fits on one chip.
Attention takes its queries in blocks.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["Dims", "dims", "moe", "route_gap", "forward_logits"]

F32 = jnp.float32


class Dims(NamedTuple):
    d: int
    heads: int
    kv_heads: int
    d_inner: int
    state: int
    ssm_head: int
    conv: int
    ff: int
    shared_ff: int
    top_k: int
    held: int
    offset: int
    eps: float
    attn_scale: float


def dims(cfg: dict) -> Dims:
    """The shapes and constants of a granitemoehybrid configuration."""
    return Dims(d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                d_inner=cfg["mamba_expand"] * cfg["hidden_size"],
                state=cfg["mamba_d_state"], ssm_head=cfg["mamba_d_head"],
                conv=cfg["mamba_d_conv"], ff=cfg["intermediate_size"],
                shared_ff=cfg["shared_intermediate_size"],
                top_k=cfg["num_experts_per_tok"],
                held=cfg["num_local_experts"], offset=cfg["expert_offset"],
                eps=cfg["rms_norm_eps"],
                attn_scale=cfg["attention_multiplier"])


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _f32(w):
    return {k: v.astype(F32) for k, v in w.items()}


def _mamba(dm: Dims, w, x):
    S = x.shape[0]
    di, N, P = dm.d_inner, dm.state, dm.ssm_head
    nh = di // P
    z, xbc, dt = jnp.split(x @ w["in_proj"].T, [di, 2 * di + 2 * N], axis=-1)
    padded = jnp.concatenate([jnp.zeros((dm.conv - 1, xbc.shape[1]), F32), xbc])
    kernel = w["conv1d_weight"][:, 0, :]
    xbc = jax.nn.silu(sum(padded[j:j + S] * kernel[:, j]
                          for j in range(dm.conv)) + w["conv1d_bias"])
    xs, Bm, Cm = jnp.split(xbc, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    xs = xs.reshape(S, nh, P)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = h * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t
        return h, h @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((nh, P, N), F32), (xs, dt, Bm, Cm))
    y = (y + xs * w["D"][:, None]).reshape(S, di)
    return _rms(y * jax.nn.silu(z), w["norm"], dm.eps) @ w["out_proj"].T


def _attention(dm: Dims, w, x, block=512):
    S = x.shape[0]
    H, KV = dm.heads, dm.kv_heads
    dh = dm.d // H
    q = (x @ w["q_proj"].T).reshape(S, H, dh)
    k = jnp.repeat((x @ w["k_proj"].T).reshape(S, KV, dh), H // KV, axis=1)
    v = jnp.repeat((x @ w["v_proj"].T).reshape(S, KV, dh), H // KV, axis=1)
    out = []
    for lo in range(0, S, block):
        qb = q[lo:lo + block]
        s = jnp.einsum("qhd,khd->hqk", qb, k) * dm.attn_scale
        seen = jnp.arange(S)[None, :] <= jnp.arange(lo, lo + len(qb))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v))
    return jnp.concatenate(out).reshape(S, H * dh) @ w["o_proj"].T


def moe(dm: Dims, w, x, ids=None):
    """The held routed experts and the shared expert on x (S, d), with
    float32 weights ``w``: every held expert on every token, weighted by
    its gate (0 where the token did not choose it).  Each token chooses
    ``ids`` (S, k), or the top k of the router's logits where None; the
    gates are the softmax of the router's logits over the choices."""
    logits = x @ w["router"].T
    if ids is None:
        ids = jax.lax.top_k(logits, dm.top_k)[1]
    gates = jax.nn.softmax(jnp.take_along_axis(logits, ids, axis=-1), axis=-1)
    f, fs = dm.ff, dm.shared_ff

    def expert(out, e):
        gate = jnp.sum(jnp.where(ids == dm.offset + e, gates, 0.0), axis=-1)
        hu = x @ w["input_linear"][e].T
        h = jax.nn.silu(hu[:, :f]) * hu[:, f:]
        return out + gate[:, None] * (h @ w["output_linear"][e].T), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(dm.held))
    hu = x @ w["shared_input_linear"].T
    return out + (jax.nn.silu(hu[:, :fs]) * hu[:, fs:]) @ w["shared_output_linear"].T


def route_gap(logits, ids):
    """Per token, the largest router logit left out of the choices ``ids``
    less the smallest chosen: at most 0 for a top-k choice, a little
    above 0 where the choice broke a near tie the other way."""
    chosen = jnp.any(ids[..., None] == jnp.arange(logits.shape[-1]), axis=-2)
    return jnp.max(jnp.where(chosen, -jnp.inf, logits), axis=-1) \
        - jnp.min(jnp.where(chosen, logits, jnp.inf), axis=-1)


@partial(jax.jit, static_argnums=(0, 1, 4))
def _layer(dm: Dims, kind: str, w, x, r, forced):
    """One layer; with ``forced`` (n, k), the last n tokens take those
    expert choices, and their route gaps (n,) come back too."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        mix = _mamba if kind == "mamba" else _attention
        x = x + r * mix(dm, w, _rms(x, w["input_layernorm"], dm.eps))
        h = _rms(x, w["post_attention_layernorm"], dm.eps)
        if forced is None:
            return x + r * moe(dm, w, h), None
        logits = h @ w["router"].T
        n = forced.shape[0]
        ids = jax.lax.top_k(logits, dm.top_k)[1].at[-n:].set(forced)
        return x + r * moe(dm, w, h, ids), route_gap(logits[-n:], forced)


@jax.jit
def _embed(table, tokens, mult):
    return table[tokens].astype(F32) * mult


@partial(jax.jit, static_argnums=(4,))
def _logits(table, norm, x, scaling, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ table.astype(F32).T / scaling


def forward_logits(cfg: dict, embed_tokens, final_norm, layer_weights,
                   tokens, first: int, routes=None):
    """float32 logits at positions ``first`` .. len(tokens) - 1 of the
    teacher-forced forward over ``tokens``.  ``layer_weights(i)`` gives
    layer i's bf16 weights in the published layout; each is dropped once
    its layer has run.

    With ``routes`` (layers, len(tokens) - first, k), the experts a
    program chose at those positions, each layer's MoE takes those choices
    there (the reference's own elsewhere), so that a near tie the program
    broke the other way moves no logit; returns the logits and the
    choices' route gaps (layers, len(tokens) - first), which show whether
    each was a top-k choice."""
    dm = dims(cfg)
    x = _embed(embed_tokens, tokens, float(cfg["embedding_multiplier"]))
    r = float(cfg["residual_multiplier"])
    gaps = []
    for i, kind in enumerate(cfg["layer_types"]):
        x, gap = _layer(dm, kind, layer_weights(i), x, r,
                        None if routes is None else routes[i])
        gaps.append(gap)
    logits = _logits(embed_tokens, final_norm, x[first:],
                     float(cfg["logits_scaling"]), dm.eps)
    return logits if routes is None else (logits, jnp.stack(gaps))
