"""GQA attention: full, KV-chunked (memory-bounded), local-window, and decode.

Sharding design (DESIGN.md §5): all attention tensors live in *head-major*
layout — weights (d, H, Dh) / (H, Dh, d), activations (B, S, H, Dh) — and
tensor parallelism shards the H dim.  Flat (H·Dh) sharding is never used:
for head counts not divisible by the model axis (14, 24, 40, 10 here) a
flat split cuts mid-head and every reshape to head layout forces a full
GSPMD reshard (measured: ~24 GB/device/step of spurious all-reduce on
qwen2-0.5b).  Head-dim sharding with uneven counts only pads — idle compute,
zero communication.  KV heads (≤ 8 everywhere) are replicated across the
model axis; decode KV caches shard their *sequence* axis instead
(flash-decoding split-KV; GSPMD inserts the small softmax-stat reductions).

Three execution paths share one set of weights:

* ``attend_full``   — materializes (S, S) scores; only for small tests.
* ``attend_chunked``— flash dataflow in pure JAX: outer lax.map over q
  blocks, inner lax.scan over KV blocks with online softmax.  Peak memory
  O(chunk²); what the 32k-prefill dry-run compiles.  The Pallas flash
  kernel (kernels/flash_attention) is the TPU-compiled equivalent.
* ``decode_step``   — one token against a (possibly ring-buffered) cache.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attention_init(key, cfg, dtype):
    d, h, kv, dh = cfg.d_model, cfg.heads_p, cfg.kv_heads_p, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, (d, h, dh), d, dtype),
        "wk": dense_init(k2, (d, kv, dh), d, dtype),
        "wv": dense_init(k3, (d, kv, dh), d, dtype),
        "wo": dense_init(k4, (h, dh, d), h * dh, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), dtype)
        p["bk"] = jnp.zeros((kv, dh), dtype)
        p["bv"] = jnp.zeros((kv, dh), dtype)
    return p


def attention_specs(cfg):
    p = {"wq": (None, "heads", None), "wk": (None, "kv_heads", None),
         "wv": (None, "kv_heads", None), "wo": ("heads", None, None)}
    if cfg.qkv_bias:
        p["bq"] = ("heads", None)
        p["bk"] = ("kv_heads", None)
        p["bv"] = ("kv_heads", None)
    return p


def _project_qkv(p, cfg, x, positions):
    """x (B, S, d) -> q (B,S,H,Dh) head-sharded; k/v (B,S,KV,Dh) replicated."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q, ("batch", None, "act_heads", None))
    k = constrain(k, ("batch", None, "act_kv", None))
    v = constrain(v, ("batch", None, "act_kv", None))
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def score_scale(cfg) -> float:
    """The factor on q·k: ``attention_multiplier`` where the config sets
    one (Granite 4.0), else 1/sqrt(head_dim)."""
    return cfg.attention_multiplier or 1.0 / math.sqrt(cfg.head_dim)


def _repeat_kv(t, cfg):
    """(B, S, KVp, Dh) -> (B, S, Hp, Dh); local (kv replicated, h sharded)."""
    g = cfg.heads_p // cfg.kv_heads_p
    if g == 1:
        return t
    return jnp.repeat(t, g, axis=2)


def _head_mask(cfg, dtype=jnp.float32):
    """1.0 for real heads, 0.0 for mesh-padding heads (inert slots)."""
    if cfg.heads_p == cfg.n_heads:
        return None
    return (jnp.arange(cfg.heads_p) < cfg.n_heads).astype(dtype)


def _out_proj(p, cfg, ctx, x_dtype):
    """ctx (B, S, Hp, Dh) fp32 -> (B, S, d); contraction over sharded H
    produces partials that GSPMD reduces into the seq-sharded residual.
    Mesh-padding heads are masked out (zero contribution, zero grads)."""
    mask = _head_mask(cfg, ctx.dtype)
    if mask is not None:
        ctx = ctx * mask[None, None, :, None]
    out = jnp.einsum("bshk,hkd->bsd", ctx.astype(x_dtype), p["wo"])
    return constrain(out, ("batch", "act_seq", None))


# ---------------------------------------------------------------------------
# full-materialization path (tests / small configs)
# ---------------------------------------------------------------------------

def attend_full(p, cfg, x, positions, window: int = 0):
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions)
    kh = _repeat_kv(k, cfg).astype(jnp.float32)
    vh = _repeat_kv(v, cfg).astype(jnp.float32)
    scale = score_scale(cfg)
    scores = jnp.einsum("bqhk,bshk->bhqs", q.astype(jnp.float32) * scale, kh)
    qpos = positions[..., :, None]
    kpos = positions[..., None, :]
    mask = kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[:, None] if mask.ndim == 3 else mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqs,bshk->bqhk", probs, vh)
    return _out_proj(p, cfg, ctx, x.dtype), (k, v)


# ---------------------------------------------------------------------------
# chunked online-softmax path (memory-bounded prefill)
# ---------------------------------------------------------------------------

def attend_chunked(p, cfg, x, positions, window: int = 0):
    B, S, _ = x.shape
    C = min(cfg.attn_chunk, S)
    assert S % C == 0, f"seq {S} not divisible by attn chunk {C}"
    H, Dh = cfg.heads_p, cfg.head_dim
    N = S // C
    q, k, v = _project_qkv(p, cfg, x, positions)
    scale = score_scale(cfg)
    pos2d = positions if positions.ndim == 2 else jnp.broadcast_to(
        positions[None], (B, S))
    q_blocks = jnp.moveaxis(q.reshape(B, N, C, H, Dh), 1, 0)        # N B C H Dh
    k_blocks = jnp.moveaxis(k.reshape(B, N, C, cfg.kv_heads_p, Dh), 1, 0)
    v_blocks = jnp.moveaxis(v.reshape(B, N, C, cfg.kv_heads_p, Dh), 1, 0)
    pos_blocks = jnp.moveaxis(pos2d.reshape(B, N, C), 1, 0)         # N B C
    # chunk axis stays UNsharded (it is scanned); heads stay on the model
    # axis — otherwise GSPMD reshards every lax.map slice
    q_blocks = constrain(q_blocks, (None, "batch", None, "act_heads", None))
    k_blocks = constrain(k_blocks, (None, "batch", None, "act_kv", None))
    v_blocks = constrain(v_blocks, (None, "batch", None, "act_kv", None))
    pos_blocks = constrain(pos_blocks, (None, "batch", None))

    def per_q(args):
        q_blk, qp = args                                            # (B,C,H,Dh), (B,C)
        qf = q_blk.astype(jnp.float32) * scale

        def body(carry, kv_blk):
            m, l, acc = carry
            k_blk, v_blk, kp = kv_blk
            kh = _repeat_kv(k_blk, cfg).astype(jnp.float32)         # (B,C,H,Dh)
            vh = _repeat_kv(v_blk, cfg).astype(jnp.float32)
            s = jnp.einsum("bqhk,bchk->bhqc", qf, kh)               # (B,H,C,C)
            mask = kp[:, None, :] <= qp[:, :, None]                 # (B,C,C)
            if window:
                mask &= kp[:, None, :] > qp[:, :, None] - window
            s = jnp.where(mask[:, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            pexp = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + pexp.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqc,bchk->bhqk", pexp, vh)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, C), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, C), jnp.float32)
        a0 = jnp.zeros((B, H, C, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (k_blocks, v_blocks, pos_blocks))
        out_blk = acc / jnp.maximum(l, 1e-30)[..., None]            # B H C Dh
        # cast INSIDE the map: the stacked output (and its backward
        # cotangents through moveaxis/reshape and the TP collectives they
        # feed) stays bf16 instead of f32 — §Perf iteration 1
        return jnp.moveaxis(out_blk, 1, 2).astype(x.dtype)          # B C H Dh

    outs = jax.lax.map(per_q, (q_blocks, pos_blocks))               # N B C H Dh
    ctx = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, Dh)
    return _out_proj(p, cfg, ctx, x.dtype), (k, v)


def attend(p, cfg, x, positions, window: int = 0):
    """Dispatch: chunked when the sequence is large, full otherwise."""
    S = x.shape[1]
    if S > cfg.attn_chunk and S % min(cfg.attn_chunk, S) == 0:
        return attend_chunked(p, cfg, x, positions, window)
    return attend_full(p, cfg, x, positions, window)


# ---------------------------------------------------------------------------
# decode path (single new token against a cache)
# ---------------------------------------------------------------------------

def init_cache(cfg, batch, max_seq, window: int = 0, dtype=jnp.bfloat16):
    S = min(window, max_seq) if window else max_seq
    kv, dh = cfg.kv_heads_p, cfg.head_dim
    return {
        "k": jnp.zeros((batch, S, kv, dh), dtype),
        "v": jnp.zeros((batch, S, kv, dh), dtype),
    }


def cache_specs(window: int = 0):
    # ring buffers (local attention) are small -> replicate their seq; full
    # caches shard the sequence axis over the model axis (split-KV decode)
    seq_axis = None if window else "kv_seq"
    return {"k": ("batch", seq_axis, "kv_heads", None),
            "v": ("batch", seq_axis, "kv_heads", None)}


def decode_step(p, cfg, x, cache, pos, window: int = 0):
    """x (B, 1, d); pos scalar int32.  Returns (out, new cache)."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, cfg, x, positions)
    S = cache["k"].shape[1]
    slot = (pos % S) if window else pos
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, slot, 0, 0))
    kh = _repeat_kv(k, cfg).astype(jnp.float32)                     # (B,S,H,Dh)
    vh = _repeat_kv(v, cfg).astype(jnp.float32)
    scale = score_scale(cfg)
    s = jnp.einsum("bqhk,bshk->bhqs", q.astype(jnp.float32) * scale, kh)
    idx = jnp.arange(S)
    if window:
        age = (slot - idx) % S                     # 0 = newest
        valid = age <= jnp.minimum(pos, S - 1)
    else:
        valid = idx <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqs,bshk->bqhk", probs, vh)
    mask = _head_mask(cfg, ctx.dtype)
    if mask is not None:
        ctx = ctx * mask[None, None, :, None]
    out = jnp.einsum("bqhk,hkd->bqd", ctx.astype(x.dtype), p["wo"])
    return out, {"k": k, "v": v}


def prefill_into_cache(p, cfg, x, positions, cache, window: int = 0):
    """Run chunked/full attention AND write K/V into the decode cache."""
    out, (k, v) = attend(p, cfg, x, positions, window)
    S_new = k.shape[1]
    S_cache = cache["k"].shape[1]
    if window and S_new >= S_cache:
        start = S_new - S_cache
        k_keep = jax.lax.dynamic_slice_in_dim(k, start, S_cache, axis=1)
        v_keep = jax.lax.dynamic_slice_in_dim(v, start, S_cache, axis=1)
        # ring alignment: slot of absolute position p is p % S_cache
        roll = (S_new % S_cache)
        k_keep = jnp.roll(k_keep, roll, axis=1)
        v_keep = jnp.roll(v_keep, roll, axis=1)
        cache = {"k": k_keep.astype(cache["k"].dtype),
                 "v": v_keep.astype(cache["v"].dtype)}
    else:
        cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
        }
    return out, cache
