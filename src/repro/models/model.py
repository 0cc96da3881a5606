"""Language-model API: init / loss / prefill / decode across all 10 archs.

Modality frontends ([audio]/[vlm] archs) are stubs per the assignment: the
first ``cfg.n_prefix`` sequence positions take precomputed frame/patch
embeddings (supplied by ``input_specs``) instead of token embeddings; the
loss masks those positions.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.metrics import span
from repro.distributed.sharding import constrain
from repro.models import transformer as tf
from repro.models.layers import (embed_tokens, embedding_init, embedding_specs,
                                 logits_head, norm_init, norm_specs,
                                 sinusoidal_pos_emb)

__all__ = ["init_params", "param_specs", "forward", "loss_fn", "prefill",
           "decode_step", "cache_init", "cache_specs", "moe_counters",
           "moe_routes", "serve_request"]


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def init_params(key, cfg):
    dtype = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    return {
        "embedding": embedding_init(k1, cfg, dtype),
        "stack": tf.stack_init(k2, cfg, dtype),
        "final_norm": norm_init(cfg.d_model, cfg.norm_type, dtype),
    }


def param_specs(cfg):
    return {
        "embedding": embedding_specs(cfg),
        "stack": tf.stack_specs(cfg),
        "final_norm": norm_specs(cfg.norm_type),
    }


def _embed(params, cfg, tokens):
    x = embed_tokens(params["embedding"], tokens).astype(_dtype(cfg))
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _logits(params, cfg, x):
    logits = logits_head(params["embedding"], cfg, x)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _embed_inputs(params, cfg, tokens, embeds, positions):
    x = _embed(params, cfg, tokens)
    if cfg.frontend is not None and embeds is not None:
        x = jax.lax.dynamic_update_slice(
            x, embeds.astype(x.dtype), (0, 0, 0))
    if cfg.pos_emb == "sinusoidal":
        x = x + sinusoidal_pos_emb(positions, cfg.d_model).astype(x.dtype)
    return constrain(x, ("batch", "act_seq", None))


def forward(params, cfg, tokens, embeds=None):
    """tokens (B, S) -> logits (B, S, V) float32."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = _embed_inputs(params, cfg, tokens, embeds, positions)
    x, _ = tf.apply_stack(params["stack"], cfg, x, positions)
    x = tf.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    return _logits(params, cfg, x)


def _xent(logits, labels, mask):
    """logits (B,S,V) fp32, labels (B,S) int32, mask (B,S) -> mean nll."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn(params, cfg, batch):
    """batch: {"tokens": (B,S) int32, optional "embeds": (B,n_prefix,d)}.
    Next-token prediction; frontend-prefix positions are masked out."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = _embed_inputs(params, cfg, tokens, batch.get("embeds"), positions)
    x, _ = tf.apply_stack(params["stack"], cfg, x, positions)
    x = tf.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    labels = tokens[:, 1:]
    mask = (positions[:, 1:] >= cfg.n_prefix).astype(jnp.float32)
    h = x[:, :-1]
    if cfg.loss_chunk and (S - 1) % cfg.loss_chunk == 0 and S - 1 > cfg.loss_chunk:
        # chunk the vocab projection over the sequence: peak memory is one
        # (B, chunk, V) logits block instead of (B, S, V)
        C = cfg.loss_chunk
        N = (S - 1) // C
        hc = jnp.moveaxis(h.reshape(B, N, C, -1), 1, 0)
        lc = jnp.moveaxis(labels.reshape(B, N, C), 1, 0)
        mc = jnp.moveaxis(mask.reshape(B, N, C), 1, 0)

        def chunk_loss(carry, inp):
            hb, lb, mb = inp
            logits = _logits(params, cfg, hb)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
            nll, msum = carry
            return (nll + ((logz - gold) * mb).sum(), msum + mb.sum()), None

        (nll, msum), _ = jax.lax.scan(chunk_loss, (0.0, 0.0), (hc, lc, mc))
        return nll / jnp.maximum(msum, 1.0)
    logits = _logits(params, cfg, h)
    return _xent(logits, labels, mask)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def cache_init(cfg, batch, cache_len, dtype=None):
    return tf.stack_cache_init(cfg, batch, cache_len, dtype or _dtype(cfg))


def cache_specs(cfg):
    return tf.stack_cache_specs(cfg)


def prefill(params, cfg, tokens, cache_len=None, embeds=None):
    """Process a prompt, returning (last-position logits, filled caches)."""
    B, S = tokens.shape
    cache_len = cache_len or S
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = _embed_inputs(params, cfg, tokens, embeds, positions)
    caches = cache_init(cfg, B, cache_len)
    x, caches = tf.apply_stack(params["stack"], cfg, x, positions, caches)
    x = tf.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    logits = _logits(params, cfg, x[:, -1:])
    return logits[:, 0], caches


def decode_step(params, cfg, token, caches, pos):
    """token (B,) int32; pos scalar int32 (position of this token).
    Returns (logits (B, V) fp32, new caches)."""
    B = token.shape[0]
    x = _embed(params, cfg, token[:, None])
    if cfg.pos_emb == "sinusoidal":
        posv = jnp.full((B, 1), pos, jnp.int32)
        x = x + sinusoidal_pos_emb(posv, cfg.d_model).astype(x.dtype)
    x, caches = tf.decode_stack(params["stack"], cfg, x, caches, pos)
    x = tf.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    logits = _logits(params, cfg, x)
    return logits[:, 0], caches


# ---------------------------------------------------------------------------
# one request, as a serving engine steps it
# ---------------------------------------------------------------------------

def moe_counters(caches):
    """Summed ``pairs`` and largest ``max_load`` over the served-MoE layers
    of ``caches`` (the counters of each layer's last call); zeros if none."""
    layers = list(caches["groups"].values()) + list(caches["tail"])
    found = [c["moe"] for c in layers if "moe" in c]
    zero = jnp.zeros((), jnp.int32)
    return {"pairs": sum((jnp.sum(c["pairs"]) for c in found), zero),
            "max_load": jnp.max(jnp.stack(
                [zero] + [jnp.max(c["max_load"]) for c in found]))}


def moe_routes(cfg, caches):
    """The experts each served-MoE layer of ``caches`` chose at its last
    position, (B, layers, k) in layer order; None if there is none."""
    groups = [caches["groups"][key]["moe"]["ids"] for key in
              (f"b{i}_{kind}" for i, kind in enumerate(cfg.block_pattern))
              if "moe" in caches["groups"][key]]
    layers = []
    if groups:
        g = jnp.stack(groups, axis=1)                  # (groups, blocks, B, k)
        layers.append(g.reshape((-1,) + g.shape[2:]))
    layers += [c["moe"]["ids"][None] for c in caches["tail"] if "moe" in c]
    return jnp.moveaxis(jnp.concatenate(layers), 0, 1) if layers else None


def _add_counters(a, b):
    return {"pairs": a["pairs"] + b["pairs"],
            "max_load": jnp.maximum(a["max_load"], b["max_load"])}


@partial(jax.jit, static_argnums=(1, 3))
def lm_prefill(params, cfg, tokens, n_new):
    """Prompts (B, S) into caches of S + n_new positions; the decode state
    that ``lm_decode_token`` steps, and the prompts' last logits (B, V)."""
    B, S = tokens.shape
    logits, caches = prefill(params, cfg, tokens, cache_len=S + n_new)
    state = {"caches": caches,
             "tok": jnp.argmax(logits, axis=-1).astype(jnp.int32),
             "pos": jnp.asarray(S, jnp.int32), "i": jnp.zeros((), jnp.int32),
             "logits": jnp.zeros((B, n_new, logits.shape[-1]), jnp.float32),
             "tokens": jnp.zeros((B, n_new), jnp.int32),
             "counters": moe_counters(caches), "routes": None}
    routes = moe_routes(cfg, caches)
    if routes is not None:
        state["routes"] = jnp.zeros((B, n_new + 1) + routes.shape[1:],
                                    jnp.int32).at[:, 0].set(routes)
    return logits, state


@partial(jax.jit, static_argnums=(1,), donate_argnums=(2,))
def lm_decode_token(params, cfg, state):
    """One greedy token: feeds ``state["tok"]`` at ``state["pos"]`` and
    keeps its logits and the next token on the device."""
    logits, caches = decode_step(params, cfg, state["tok"], state["caches"],
                                 state["pos"])
    i, routes = state["i"], state["routes"]
    if routes is not None:
        routes = routes.at[:, i + 1].set(moe_routes(cfg, caches))
    return {"caches": caches,
            "tok": jnp.argmax(logits, axis=-1).astype(jnp.int32),
            "pos": state["pos"] + 1, "i": i + 1,
            "logits": state["logits"].at[:, i].set(logits),
            "tokens": state["tokens"].at[:, i].set(state["tok"]),
            "counters": _add_counters(state["counters"], moe_counters(caches)),
            "routes": routes}


def serve_request(params, cfg, tokens, n_new):
    """A batch of requests: the prompts ``tokens`` (B, S) int32 prefilled
    in one program, then ``n_new`` greedy tokens decoded one program each,
    the tokens staying on the device.  Returns, on the device,
    ``prefill_logits`` (B, V), ``logits`` (B, n_new, V) after each fed
    token, ``tokens`` (B, n_new) the tokens fed, the served MoE's
    counters summed over the batch (``moe_pairs``; ``moe_max_load`` the
    largest), and ``routes`` (B, 1 + n_new, layers, k), the experts each
    served-MoE layer chose at the prompt's last position and at each fed
    token (None without such layers).  Nothing waits for the device."""
    with span("lm.prefill"):
        prefill_logits, state = lm_prefill(params, cfg, tokens, n_new)
    with span("lm.decode"):
        for _ in range(n_new):
            state = lm_decode_token(params, cfg, state)
    return {"prefill_logits": prefill_logits, "logits": state["logits"],
            "tokens": state["tokens"],
            "moe_pairs": state["counters"]["pairs"],
            "moe_max_load": state["counters"]["max_load"],
            "routes": state["routes"]}
