"""Mixture-of-Experts layer: top-k routing + capacity-bounded dispatch.

TPU-native design notes (DESIGN.md §2): GShard's one-hot einsum dispatch
costs 2·T·(Tg·k·cf)·d FLOPs — at E=128/top-8 that is ~30-100× the expert
GEMMs themselves, so we use *index-based* dispatch instead: a tiny int32
slot table (invert token→(expert, position) with a scatter), then gather
token rows into per-expert capacity buffers.  Zero matmul overhead; the
moved bytes are O(T·k·d).

Expert parallelism runs in ``shard_map`` — manual over the ``model`` mesh
axis (experts sharded E_loc = E/|model|), auto over data/pod (the batch dim
stays GSPMD-managed).  Per device:

    all_gather(x, model)               # residual arrives sequence-sharded
    route on the full local batch      # deterministic, replicated compute
    gather rows for MY experts → FFN   # (B, E_loc, C, d)
    scatter-add weighted outputs       # partial (B, S, d)
    psum_scatter(out, model)           # back to sequence-sharded residual

Capacity is per sequence: C = ceil(S·k·cf / E) — routing never crosses the
batch dim, so data sharding needs no token exchange (DP×EP grid).  Dropped
tokens (position ≥ C) pass through the residual untouched.

``apply_moe_local`` is the identical math on one device (E_loc = E); it is
the CPU test path and the oracle for the sharded path.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import constrain, current_rules
from repro.models.layers import dense_init

__all__ = ["moe_init", "moe_specs", "apply_moe", "apply_moe_local",
           "apply_moe_ref", "moe_capacity"]


def moe_init(key, cfg, dtype):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.experts_p
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": dense_init(k1, (d, E), d, jnp.float32),
        "w_gate": dense_init(k2, (E, d, f), d, dtype),
        "w_up": dense_init(k3, (E, d, f), d, dtype),
        "w_down": dense_init(k4, (E, f, d), f, dtype),
    }


def moe_specs(cfg):
    # expert weights are FSDP-sharded over the data axis ("fsdp") in addition
    # to expert parallelism — 470 GB of qwen3-moe experts fit 256 chips only
    # as E/16 × d/16 shards; the full (E_loc, d, f) panel is all-gathered
    # per layer inside shard_map (ZeRO-3 weight gathering).
    return {"router": (None, None),
            "w_gate": ("experts", "fsdp", None),
            "w_up": ("experts", "fsdp", None),
            "w_down": ("experts", "fsdp", None)}


def moe_capacity(cfg, seq_len: int) -> int:
    c = math.ceil(seq_len * cfg.experts_per_token * cfg.capacity_factor
                  / cfg.n_experts)
    return max(4, min(int(c), seq_len)) if seq_len > 1 else cfg.experts_per_token


# ---------------------------------------------------------------------------
# routing: token -> (expert, position-in-expert) with per-sequence capacity
# ---------------------------------------------------------------------------

def _route(cfg, x, router, capacity):
    """x (B, S, d) -> gates (B,S,k), slot (B,S,k) in [0, E*C] (E*C = dropped),
    slot_token (B, E*C+1) int32 inverse table (token index per slot, S = empty).
    """
    B, S, _ = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    C = capacity
    logits = (x.astype(jnp.float32) @ router)                        # (B,S,Ep)
    if router.shape[1] != E:
        # mesh-padding experts are never routed to
        pad_mask = jnp.arange(router.shape[1]) >= E
        logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
        logits = logits[..., :]  # keep Ep width; padded cols softmax to ~0
    gates_full = jax.nn.softmax(logits, axis=-1)
    gk, ik = jax.lax.top_k(gates_full, k)                            # (B,S,k)
    gk = gk / jnp.maximum(gk.sum(axis=-1, keepdims=True), 1e-9)
    # position-in-expert: priority by (k, token): all rank-0 choices first
    counts = jnp.zeros((B, E), jnp.int32)
    pos = []
    for j in range(k):
        oh = jax.nn.one_hot(ik[:, :, j], E, dtype=jnp.int32)         # (B,S,E)
        within = jnp.cumsum(oh, axis=1) - oh                         # rank among same-k
        pos_j = jnp.take_along_axis(counts, ik[:, :, j], axis=1) \
            + jnp.take_along_axis(within, ik[:, :, j][..., None], axis=2)[..., 0]
        pos.append(pos_j)
        counts = counts + oh.sum(axis=1)
    pos = jnp.stack(pos, axis=-1)                                    # (B,S,k)
    dropped = pos >= C
    Etab = cfg.experts_p       # slot table spans padded experts (empty rows)
    slot = jnp.where(dropped, Etab * C, ik * C + pos)                # (B,S,k)
    # invert: slot -> token index (scatter; last write wins, slots unique)
    token_ids = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :, None],
                                 (B, S, k)).reshape(B, S * k)
    slot_token = jnp.full((B, Etab * C + 1), S, jnp.int32)
    slot_token = jax.vmap(
        lambda st, sl, ti: st.at[sl].set(ti, mode="drop")
    )(slot_token, slot.reshape(B, S * k), token_ids)
    return gk, slot, slot_token, gates_full


def _expert_ffn(cfg, w_gate, w_up, w_down, xin):
    """xin (B, E_loc, C, d) -> (B, E_loc, C, d); SwiGLU per expert."""
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xin, w_gate)) \
        * jnp.einsum("becd,edf->becf", xin, w_up)
    return jnp.einsum("becf,efd->becd", h, w_down)


def _moe_core(cfg, p, x, capacity, e_lo, e_n):
    """Local MoE math for experts [e_lo, e_lo + e_n); x (B, S, d) full-seq."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    C = capacity
    gk, slot, slot_token, _ = _route(cfg, x, p["router"], C)
    # my slice of the slot table
    my_slots = jax.lax.dynamic_slice_in_dim(slot_token, e_lo * C, e_n * C, axis=1)
    valid = my_slots < S                                             # (B, e_n*C)
    tok = jnp.where(valid, my_slots, 0)
    xin = jax.vmap(lambda xb, tb: xb[tb])(x, tok)                    # (B, e_n*C, d)
    xin = jnp.where(valid[..., None], xin, 0.0).reshape(B, e_n, C, d)
    w_gate = jax.lax.dynamic_slice_in_dim(p["w_gate"], e_lo, e_n, axis=0)
    w_up = jax.lax.dynamic_slice_in_dim(p["w_up"], e_lo, e_n, axis=0)
    w_down = jax.lax.dynamic_slice_in_dim(p["w_down"], e_lo, e_n, axis=0)
    h = _expert_ffn(cfg, w_gate, w_up, w_down, xin).reshape(B, e_n * C, d)
    h = jnp.where(valid[..., None], h, 0.0)
    # combine: scatter weighted expert outputs back to token rows.
    # gate per slot: slot -> (token t, rank j) via gk gathered by my_slots
    flat_gate = jnp.zeros((B, cfg.experts_p * C + 1), gk.dtype)
    flat_gate = jax.vmap(
        lambda fg, sl, g: fg.at[sl].set(g, mode="drop")
    )(flat_gate, slot.reshape(B, S * k), gk.reshape(B, S * k))
    my_gates = jax.lax.dynamic_slice_in_dim(flat_gate, e_lo * C, e_n * C, axis=1)
    weighted = h * my_gates[..., None].astype(h.dtype)
    out = jnp.zeros((B, S, d), h.dtype)
    out = jax.vmap(
        lambda ob, tb, hb: ob.at[tb].add(hb, mode="drop")
    )(out, tok, jnp.where(valid[..., None], weighted, 0.0))
    return out


def apply_moe_local(p, cfg, x, capacity=None):
    """Single-device path (CPU tests; oracle for the sharded path)."""
    C = capacity or moe_capacity(cfg, x.shape[1])
    return _moe_core(cfg, p, x.astype(jnp.float32).astype(x.dtype), C,
                     0, cfg.n_experts).astype(x.dtype)


def apply_moe(p, cfg, x):
    """Dispatch: shard_map expert parallelism when a mesh with a >1 'model'
    axis is active; local math otherwise.

    Full-manual shard_map over every mesh axis (the partial-manual
    ``axis_names`` mode miscompiles on the CPU backend): batch stays sharded
    over the data/pod axes (routing is per-sequence, so data shards never
    exchange tokens), experts shard over 'model', and the sequence-sharded
    residual is all-gathered in / psum-scattered out — the Megatron-SP
    pattern made explicit."""
    rules = current_rules()
    mesh = getattr(rules, "mesh", None) if rules is not None else None
    if mesh is None or mesh.shape.get("model", 1) == 1:
        return apply_moe_local(p, cfg, x)

    B, S, d = x.shape
    n_model = mesh.shape["model"]
    C = moe_capacity(cfg, S)
    E = cfg.n_experts
    e_per = cfg.experts_p // n_model   # padded expert count divides exactly
    seq_sharded = S > 1 and S % n_model == 0
    batch_axes = rules.resolve("batch")   # ("pod","data") | "data" | None

    use_a2a = (seq_sharded
               and getattr(rules, "table", {}).get("moe_dispatch") == "a2a")

    def shard_fn(p_loc, x_loc):
        midx = jax.lax.axis_index("model")
        # FSDP weight gathering: (E_loc, d/|data|, f) -> (E_loc, d, f)
        p_full = dict(p_loc)
        for w in ("w_gate", "w_up", "w_down"):
            p_full[w] = jax.lax.all_gather(p_loc[w], "data", axis=1, tiled=True)
        e_lo = midx * e_per
        if use_a2a:
            return _moe_a2a(cfg, p_full, x_loc, n_model, e_per)
        if seq_sharded:
            xf = jax.lax.all_gather(x_loc, "model", axis=1, tiled=True)
        else:
            xf = x_loc
        out_partial = _moe_core_padded(cfg, p_full, xf, C, e_lo, e_per, E)
        if seq_sharded:
            return jax.lax.psum_scatter(out_partial, "model",
                                        scatter_dimension=1, tiled=True)
        return jax.lax.psum(out_partial, "model")

    x_spec = P(batch_axes, "model" if seq_sharded else None, None)
    p_specs = {"router": P(None, None), "w_gate": P("model", "data", None),
               "w_up": P("model", "data", None), "w_down": P("model", "data", None)}
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(p_specs, x_spec),
                       out_specs=x_spec, check_vma=False)
    return fn(p, x).astype(x.dtype)


def _moe_a2a(cfg, p_full, x_loc, n_model, e_per):
    """All-to-all token dispatch (§Perf iteration 7; GShard/Switch topology).

    Each model shard routes ONLY its own sequence slice (router replicated —
    no x all-gather), packs rows into per-destination capacity buffers,
    exchanges them with one all-to-all, runs its local experts, and reverses
    the exchange.  Wire per layer ≈ 2 × routed-row bytes (k·cf·tokens/16)
    instead of all-gather + psum-scatter of the full residual.  Capacity is
    per (source shard, expert): C_loc = ceil(S_loc·k·cf/E) — a documented
    variant of per-sequence capacity (standard in deployed MoE systems).
    """
    B, S_loc, d = x_loc.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    Etab = cfg.experts_p
    C_loc = max(4, math.ceil(S_loc * k * cfg.capacity_factor / E))
    gk, slot, slot_token, _ = _route(cfg, x_loc, p_full["router"], C_loc)
    valid = slot_token < S_loc                                    # (B, Etab*C+1)
    tok = jnp.where(valid, slot_token, 0)
    rows = jax.vmap(lambda xb, tb: xb[tb])(x_loc, tok)            # (B, Etab*C+1, d)
    rows = jnp.where(valid[..., None], rows, 0.0)
    send = rows[:, :Etab * C_loc].reshape(B, n_model, e_per * C_loc, d)
    send = jnp.moveaxis(send, 1, 0)                               # (n_model, B, eC, d)
    recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                              tiled=True)                         # (n_model, B, eC, d)
    # my experts' rows from every source shard
    xin = jnp.moveaxis(recv, 0, 1).reshape(B, n_model, e_per, C_loc, d)
    xin = jnp.moveaxis(xin, 1, 2).reshape(B, e_per, n_model * C_loc, d)
    h = _expert_ffn(cfg, p_full["w_gate"], p_full["w_up"], p_full["w_down"],
                    xin)                                          # (B, e_per, nC, d)
    # reverse exchange
    h = jnp.moveaxis(h.reshape(B, e_per, n_model, C_loc, d), 2, 1)
    h = jnp.moveaxis(h.reshape(B, n_model, e_per * C_loc, d), 1, 0)
    back = jax.lax.all_to_all(h, "model", split_axis=0, concat_axis=0,
                              tiled=True)
    got = jnp.moveaxis(back, 0, 1).reshape(B, Etab * C_loc, d)
    got = jnp.concatenate([got, jnp.zeros((B, 1, d), got.dtype)], axis=1)
    # combine with gates, scatter back to local token rows
    flat_gate = jnp.zeros((B, Etab * C_loc + 1), gk.dtype)
    flat_gate = jax.vmap(
        lambda fg, sl, g: fg.at[sl].set(g, mode="drop")
    )(flat_gate, slot.reshape(B, S_loc * k), gk.reshape(B, S_loc * k))
    weighted = got * flat_gate[..., None].astype(got.dtype)
    out = jnp.zeros((B, S_loc, d), got.dtype)
    out = jax.vmap(
        lambda ob, tb, hb: ob.at[tb].add(hb, mode="drop")
    )(out, tok, jnp.where(valid[..., None], weighted, 0.0))
    return out


def _moe_core_padded(cfg, p_loc, x, capacity, e_lo, e_n, total_e):
    """_moe_core against locally-sliced expert weights (already E_loc rows),
    masking experts beyond ``total_e`` (padding shards)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    C = capacity
    gk, slot, slot_token, _ = _route(cfg, x, p_loc["router"], C)
    my_slots = jax.lax.dynamic_slice_in_dim(slot_token, e_lo * C, e_n * C, axis=1)
    valid = my_slots < S
    tok = jnp.where(valid, my_slots, 0)
    xin = jax.vmap(lambda xb, tb: xb[tb])(x, tok)
    xin = jnp.where(valid[..., None], xin, 0.0).reshape(B, e_n, C, d)
    h = _expert_ffn(cfg, p_loc["w_gate"], p_loc["w_up"], p_loc["w_down"],
                    xin).reshape(B, e_n * C, d)
    h = jnp.where(valid[..., None], h, 0.0)
    flat_gate = jnp.zeros((B, cfg.experts_p * C + 1), gk.dtype)
    flat_gate = jax.vmap(
        lambda fg, sl, g: fg.at[sl].set(g, mode="drop")
    )(flat_gate, slot.reshape(B, S * k), gk.reshape(B, S * k))
    my_gates = jax.lax.dynamic_slice_in_dim(flat_gate, e_lo * C, e_n * C, axis=1)
    weighted = h * my_gates[..., None].astype(h.dtype)
    out = jnp.zeros((B, S, d), h.dtype)
    out = jax.vmap(
        lambda ob, tb, hb: ob.at[tb].add(hb, mode="drop")
    )(out, tok, jnp.where(valid[..., None], weighted, 0.0))
    return out


def apply_moe_ref(p, cfg, x):
    """Dropless dense reference: every expert on every token, gate-masked.
    O(T·E·d·f) — tiny test sizes only.  Capacity-dropping in the real path
    means outputs match only when capacity is not exceeded."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    logits = x.astype(jnp.float32) @ p["router"]
    gates_full = jax.nn.softmax(logits, axis=-1)
    gk, ik = jax.lax.top_k(gates_full, k)
    gk = gk / jnp.maximum(gk.sum(axis=-1, keepdims=True), 1e-9)
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for e in range(E):
        h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        ye = (h @ p["w_down"][e]).astype(jnp.float32)
        gate_e = jnp.where(ik == e, gk, 0.0).sum(axis=-1)            # (B,S)
        out = out + ye * gate_e[..., None]
    return out.astype(x.dtype)
