"""Block composition + scan-over-layers stack.

A config's layer sequence is ``block_pattern × n_groups + tail_pattern``.
The repeated group is executed under ``lax.scan`` with stacked params
(leading ``n_groups`` axis) and a configurable remat policy — this keeps the
HLO small (compile time O(1) in depth) and bounds activation memory; the
tail layers run unrolled.

Block kinds: ``attn`` (GQA + MLP), ``local_attn`` (windowed), ``moe``
(GQA + expert MLP), ``ssm`` (Mamba-2, single residual), ``rglru``
(Griffin recurrent + MLP), and the Granite 4.0 kinds ``ssm_moe`` and
``attn_moe``: a Mamba-2 or GQA mixer followed by the served MoE (dropless,
held experts, shared expert), whose counters and last position's
routing ride in the layer's cache.
A block is its mixer, then its FFN (if any), each a pre-norm residual
branch scaled by ``cfg.residual_multiplier``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, apply_norm, mlp_init, mlp_specs, \
    norm_init, norm_specs

__all__ = ["block_init", "block_specs", "apply_block", "block_cache_init",
           "block_cache_specs", "decode_block", "stack_init", "stack_specs",
           "apply_stack", "stack_cache_init", "stack_cache_specs",
           "decode_stack"]

# kind -> (mixer, ffn); the mixer's params live under its own key
_KINDS = {"attn": ("attn", "mlp"), "local_attn": ("attn", "mlp"),
          "moe": ("attn", "moe"), "ssm": ("ssm", None),
          "rglru": ("rec", "mlp"), "ssm_moe": ("ssm", "served_moe"),
          "attn_moe": ("attn", "served_moe")}


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def block_init(key, cfg, kind, dtype):
    if kind not in _KINDS:
        raise ValueError(kind)
    mixer, ffn = _KINDS[kind]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"norm1": norm_init(cfg.d_model, cfg.norm_type, dtype)}
    if mixer == "attn":
        p["attn"] = attn_mod.attention_init(k1, cfg, dtype)
    elif mixer == "ssm":
        p["ssm"] = ssm_mod.ssm_init(k3, cfg, dtype)
    else:
        p["rec"] = rglru_mod.rglru_init(k4, cfg, dtype)
    if ffn:
        p["norm2"] = norm_init(cfg.d_model, cfg.norm_type, dtype)
        p["ffn"] = {"mlp": mlp_init, "moe": moe_mod.moe_init,
                    "served_moe": moe_mod.served_moe_init}[ffn](k2, cfg, dtype)
    return p


def block_specs(cfg, kind):
    mixer, ffn = _KINDS[kind]
    p = {"norm1": norm_specs(cfg.norm_type),
         mixer: {"attn": attn_mod.attention_specs, "ssm": ssm_mod.ssm_specs,
                 "rec": rglru_mod.rglru_specs}[mixer](cfg)}
    if ffn:
        p["norm2"] = norm_specs(cfg.norm_type)
        p["ffn"] = {"mlp": mlp_specs, "moe": moe_mod.moe_specs,
                    "served_moe": moe_mod.served_moe_specs}[ffn](cfg)
    return p


def _res(cfg, x):
    return constrain(x, ("batch", "act_seq", None))


def _branch(cfg, y):
    m = cfg.residual_multiplier
    return y if m == 1.0 else y * jnp.asarray(m, y.dtype)


def _apply_ffn(p, cfg, ffn, h):
    """FFN output and, for the served MoE, its counters (else None)."""
    if ffn == "served_moe":
        return moe_mod.apply_moe_served(p, cfg, h)
    if ffn == "moe":
        return moe_mod.apply_moe(p, cfg, h), None
    return apply_mlp(p, cfg, h), None


def _with_counters(cache, counters):
    return cache if counters is None else {**cache, "moe": counters}


def apply_block(p, cfg, kind, x, positions, cache=None):
    """Training/prefill forward.  Returns (x, cache_or_None)."""
    mixer, ffn = _KINDS[kind]
    window = cfg.local_window if kind == "local_attn" else 0
    new_cache = None
    h = apply_norm(p["norm1"], x, cfg.norm_type, cfg.norm_eps)
    if mixer == "attn":
        if cache is not None:
            a, new_cache = attn_mod.prefill_into_cache(
                p["attn"], cfg, h, positions, cache, window)
        else:
            a, _ = attn_mod.attend(p["attn"], cfg, h, positions, window)
    else:
        apply = ssm_mod.apply_ssm if mixer == "ssm" else rglru_mod.apply_rglru
        if cache is not None:
            a, (hT, conv) = apply(p[mixer], cfg, h, return_state=True)
            new_cache = {"h": hT, "conv": conv.astype(cache["conv"].dtype)}
        else:
            a = apply(p[mixer], cfg, h)
    x = _res(cfg, x + _branch(cfg, a))
    if ffn:
        h = apply_norm(p["norm2"], x, cfg.norm_type, cfg.norm_eps)
        f, counters = _apply_ffn(p["ffn"], cfg, ffn, h)
        x = _res(cfg, x + _branch(cfg, f))
        if new_cache is not None:
            new_cache = _with_counters(new_cache, counters)
    return x, new_cache


def _counters_init(cfg, batch):
    return {"pairs": jnp.zeros((), jnp.int32),
            "max_load": jnp.zeros((), jnp.int32),
            "ids": jnp.zeros((batch, cfg.experts_per_token), jnp.int32)}


def block_cache_init(cfg, kind, batch, cache_len, dtype=jnp.bfloat16):
    mixer, ffn = _KINDS[kind]
    if mixer == "attn":
        window = cfg.local_window if kind == "local_attn" else 0
        cache = attn_mod.init_cache(cfg, batch, cache_len, window, dtype)
    elif mixer == "ssm":
        cache = ssm_mod.ssm_cache_init(cfg, batch, dtype)
    else:
        cache = rglru_mod.rglru_cache_init(cfg, batch, dtype)
    if ffn == "served_moe":
        cache["moe"] = _counters_init(cfg, batch)
    return cache


def block_cache_specs(cfg, kind):
    mixer, ffn = _KINDS[kind]
    if mixer == "attn":
        specs = attn_mod.cache_specs(cfg.local_window if kind == "local_attn"
                                     else 0)
    elif mixer == "ssm":
        specs = ssm_mod.ssm_cache_specs(cfg)
    else:
        specs = rglru_mod.rglru_cache_specs(cfg)
    if ffn == "served_moe":
        specs["moe"] = {"pairs": (), "max_load": (), "ids": ("batch", None)}
    return specs


def decode_block(p, cfg, kind, x, cache, pos):
    """One-token decode.  x (B, 1, d); returns (x, new_cache)."""
    mixer, ffn = _KINDS[kind]
    window = cfg.local_window if kind == "local_attn" else 0
    h = apply_norm(p["norm1"], x, cfg.norm_type, cfg.norm_eps)
    mix_cache = {k: v for k, v in cache.items() if k != "moe"}
    if mixer == "attn":
        a, new_cache = attn_mod.decode_step(p["attn"], cfg, h, mix_cache, pos,
                                            window)
    elif mixer == "ssm":
        a, new_cache = ssm_mod.ssm_decode_step(p["ssm"], cfg, h, mix_cache)
    else:
        a, new_cache = rglru_mod.rglru_decode_step(p["rec"], cfg, h, mix_cache)
    x = x + _branch(cfg, a)
    if ffn:
        h = apply_norm(p["norm2"], x, cfg.norm_type, cfg.norm_eps)
        f, counters = _apply_ffn(p["ffn"], cfg, ffn, h)
        x = x + _branch(cfg, f)
        new_cache = _with_counters(new_cache, counters)
    return x, new_cache


# ---------------------------------------------------------------------------
# stacked layers: scanned groups + unrolled tail
# ---------------------------------------------------------------------------

def _group_init(key, cfg, dtype):
    ks = jax.random.split(key, len(cfg.block_pattern))
    return {f"b{i}_{kind}": block_init(ks[i], cfg, kind, dtype)
            for i, kind in enumerate(cfg.block_pattern)}


def stack_init(key, cfg, dtype):
    kg, kt = jax.random.split(key)
    groups = [
        _group_init(jax.random.fold_in(kg, g), cfg, dtype)
        for g in range(cfg.n_groups)
    ]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *groups) \
        if cfg.n_groups > 1 else jax.tree.map(lambda x: x[None], groups[0])
    tail = [block_init(jax.random.fold_in(kt, i), cfg, kind, dtype)
            for i, kind in enumerate(cfg.tail_pattern)]
    return {"groups": stacked, "tail": tail}


def stack_specs(cfg):
    group = {f"b{i}_{kind}": block_specs(cfg, kind)
             for i, kind in enumerate(cfg.block_pattern)}
    # leading layer axis is unsharded
    group = jax.tree.map(lambda spec: (None,) + tuple(spec), group,
                         is_leaf=lambda s: isinstance(s, tuple))
    tail = [block_specs(cfg, kind) for kind in cfg.tail_pattern]
    return {"groups": group, "tail": tail}


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)


def apply_stack(params, cfg, x, positions, caches=None):
    """Forward through all layers.  With ``caches`` (prefill) the per-layer
    caches are threaded and returned updated."""
    with_cache = caches is not None

    def group_fn(x, inp):
        gp, gcache = inp
        new_caches = {}
        for i, kind in enumerate(cfg.block_pattern):
            key = f"b{i}_{kind}"
            c = gcache[key] if with_cache else None
            x, nc = apply_block(gp[key], cfg, kind, x, positions, c)
            new_caches[key] = nc
        return x, (new_caches if with_cache else None)

    body = _remat(cfg, group_fn)
    if not cfg.scan_layers:
        # unrolled path: used by the roofline analysis compiles (XLA cost
        # analysis counts a scan body once — see EXPERIMENTS.md §Method)
        new_group_list = []
        for g in range(cfg.n_groups):
            gp = jax.tree.map(lambda a: a[g], params["groups"])
            gc = jax.tree.map(lambda a: a[g], caches["groups"]) if with_cache else None
            x, nc = body(x, (gp, gc))
            new_group_list.append(nc)
        new_group_caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *new_group_list)
                            if with_cache else None)
    elif with_cache:
        x, new_group_caches = jax.lax.scan(
            body, x, (params["groups"], caches["groups"]))
    else:
        x, _ = jax.lax.scan(lambda c, gp: body(c, (gp, None)), x, params["groups"])
        new_group_caches = None
    new_tail = []
    for i, kind in enumerate(cfg.tail_pattern):
        c = caches["tail"][i] if with_cache else None
        x, nc = apply_block(params["tail"][i], cfg, kind, x, positions, c)
        new_tail.append(nc)
    if with_cache:
        return x, {"groups": new_group_caches, "tail": new_tail}
    return x, None


def stack_cache_init(cfg, batch, cache_len, dtype=jnp.bfloat16):
    def group_cache(g):
        return {f"b{i}_{kind}": block_cache_init(cfg, kind, batch, cache_len, dtype)
                for i, kind in enumerate(cfg.block_pattern)}

    groups = [group_cache(g) for g in range(cfg.n_groups)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *groups) \
        if cfg.n_groups > 1 else jax.tree.map(lambda x: x[None], groups[0])
    tail = [block_cache_init(cfg, kind, batch, cache_len, dtype)
            for kind in cfg.tail_pattern]
    return {"groups": stacked, "tail": tail}


def stack_cache_specs(cfg):
    group = {f"b{i}_{kind}": block_cache_specs(cfg, kind)
             for i, kind in enumerate(cfg.block_pattern)}
    group = jax.tree.map(lambda spec: (None,) + tuple(spec), group,
                         is_leaf=lambda s: isinstance(s, tuple))
    tail = [block_cache_specs(cfg, kind) for kind in cfg.tail_pattern]
    return {"groups": group, "tail": tail}


def decode_stack(params, cfg, x, caches, pos):
    def group_fn(x, inp):
        gp, gcache = inp
        new_caches = {}
        for i, kind in enumerate(cfg.block_pattern):
            key = f"b{i}_{kind}"
            x, nc = decode_block(gp[key], cfg, kind, x, gcache[key], pos)
            new_caches[key] = nc
        return x, new_caches

    if not cfg.scan_layers:
        new_group_list = []
        for g in range(cfg.n_groups):
            gp = jax.tree.map(lambda a: a[g], params["groups"])
            gc = jax.tree.map(lambda a: a[g], caches["groups"])
            x, nc = group_fn(x, (gp, gc))
            new_group_list.append(nc)
        new_group_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *new_group_list)
        new_tail = []
        for i, kind in enumerate(cfg.tail_pattern):
            x, nc = decode_block(params["tail"][i], cfg, kind, x,
                                 caches["tail"][i], pos)
            new_tail.append(nc)
        return x, {"groups": new_group_caches, "tail": new_tail}
    x, new_group_caches = jax.lax.scan(group_fn, x,
                                       (params["groups"], caches["groups"]))
    new_tail = []
    for i, kind in enumerate(cfg.tail_pattern):
        x, nc = decode_block(params["tail"][i], cfg, kind, x, caches["tail"][i], pos)
        new_tail.append(nc)
    return x, {"groups": new_group_caches, "tail": new_tail}
