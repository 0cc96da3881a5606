"""Granite 4.0-H on the served path, against the benchmark's plain float32
reference (``bench/lm_reference.py``), at the reduced size on the CPU:
prefill and decode through the caches, the expert share, dropless routing,
and the defaults of the new multipliers.  Weights in the published layout
come from the benchmark app's seeded maker."""

import math
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import lm_reference as R  # noqa: E402
from bench.apps import granite_hybrid as app  # noqa: E402
from repro.configs import granite_4_0_h_small as G  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.models import attention as attn_mod  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402

CFG = get_config("granite-4.0-h-small", reduced=True)
KEY = jax.random.PRNGKey(7)


def _hf(cfg):
    """``cfg`` in the published ``config.json``'s keys, as the reference
    and the weight maker read it."""
    kinds = {"ssm_moe": "mamba", "attn_moe": "attention"}
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "intermediate_size": cfg.d_ff,
            "shared_intermediate_size": cfg.shared_expert_ff,
            "mamba_expand": cfg.ssm_expand, "mamba_d_state": cfg.ssm_state,
            "mamba_d_head": cfg.ssm_head_dim, "mamba_d_conv": cfg.ssm_conv,
            "num_local_experts": cfg.experts_here,
            "router_experts": cfg.n_experts,
            "expert_offset": cfg.expert_offset,
            "num_experts_per_tok": cfg.experts_per_token,
            "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
            "attention_multiplier": cfg.attention_multiplier,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "layer_types": [kinds[k] for _ in range(cfg.n_groups)
                            for k in cfg.block_pattern]}


def _weights(cfg):
    """Seeded bf16 weights in the published layout: the tied embedding,
    the final norm, and one dict per layer, in layer order."""
    hf = _hf(cfg)
    shape = app.weight_shape(hf)
    k_global, k_layers = jax.random.split(KEY)
    embed, norm = app.global_weights(shape, k_global)
    return embed, norm, [app.layer_weights(shape, t,
                                           jax.random.fold_in(k_layers, i))
                         for i, t in enumerate(hf["layer_types"])]


def _params(cfg, embed, norm, layers):
    """The program's parameter tree (``models.model.init_params``'s
    layout) from published-layout weights: each layer through
    ``layer_params``, stacked over the scanned groups."""
    P = len(cfg.block_pattern)
    groups = {f"b{j}_{kind}": jax.tree.map(
                  lambda *a: jnp.stack(a),
                  *[G.layer_params(cfg, kind, layers[g * P + j])
                    for g in range(cfg.n_groups)])
              for j, kind in enumerate(cfg.block_pattern)}
    dt = jnp.dtype(cfg.dtype)
    return {"embedding": {"tokens": embed.astype(dt)},
            "stack": {"groups": groups, "tail": []},
            "final_norm": {"scale": norm.astype(dt)}}


def _reference(cfg, embed, norm, layers, tokens, first):
    return R.forward_logits(_hf(cfg), embed, norm, layers.__getitem__,
                            tokens, first)


def _moe_reference(cfg, w, x):
    """The reference's MoE layer, shared expert included, on x (S, d)."""
    w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    return R.moe(R.dims(_hf(cfg)), w32, x)


def _prompt(cfg, n):
    return jax.random.randint(jax.random.PRNGKey(1), (n,), 0, cfg.vocab_size,
                              jnp.int32)


def _serve_against_reference(cfg, prompt_len=32, n_new=8):
    embed, norm, layers = _weights(cfg)
    params = _params(cfg, embed, norm, layers)
    tokens = _prompt(cfg, prompt_len)
    out = M.serve_request(params, cfg, tokens[None], n_new)
    fed = jnp.concatenate([tokens, out["tokens"][0]])
    want = _reference(cfg, embed, norm, layers, fed, prompt_len - 1)
    got = jnp.concatenate([out["prefill_logits"], out["logits"][0]])
    return np.asarray(got), np.asarray(want), out


def test_prefill_then_decode_matches_the_reference_at_every_position():
    """float32 weights at ``highest``: the served path (prefill into the
    SSM, conv and KV caches, then one program per token) agrees with the
    reference's full forward, teacher-forced on the generated tokens, at
    the prompt's last position and every decoded one."""
    cfg = replace(CFG, dtype="float32")
    with jax.default_matmul_precision("highest"):
        got, want, out = _serve_against_reference(cfg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the token fed at each step is the greedy choice of the logits before it
    assert np.array_equal(out["tokens"][0], np.argmax(got[:-1], axis=-1))


def test_reported_routes_are_the_reference_top_k_at_every_position():
    """float32 at ``highest``: the experts the served path reports for the
    prompt's last position and each fed token are the reference's own top
    k there (no route gap above 0), so the reference taking them changes
    no logit; a held expert put in place of one chosen in one layer shows
    as a gap above 0 there alone and moves that position's logits."""
    cfg = replace(CFG, dtype="float32")
    embed, norm, layers = _weights(cfg)
    with jax.default_matmul_precision("highest"):
        got, want, out = _serve_against_reference(cfg)
        routes = np.asarray(out["routes"][0])      # (1 + n_new, layers, k)
        assert routes.shape == (9, len(layers), cfg.experts_per_token)
        fed = jnp.concatenate([_prompt(cfg, 32), out["tokens"][0]])
        forced, gaps = R.forward_logits(
            _hf(cfg), embed, norm, layers.__getitem__, fed, 31,
            routes=jnp.moveaxis(routes, 0, 1))
        assert float(jnp.max(gaps)) <= 0.0
        np.testing.assert_allclose(np.asarray(forced), want, rtol=1e-5,
                                   atol=1e-5)
        other = routes.copy()
        held = range(cfg.expert_offset, cfg.expert_offset + cfg.experts_here)
        other[-1, 0, -1] = next(e for e in held if e not in other[-1, 0])
        moved, gaps = R.forward_logits(
            _hf(cfg), embed, norm, layers.__getitem__, fed, 31,
            routes=jnp.moveaxis(other, 0, 1))
    gaps = np.asarray(gaps)
    assert gaps[0, -1] > 0 and (np.delete(gaps.ravel(), gaps.shape[1] - 1)
                                <= 0).all()
    assert np.abs(np.asarray(moved[-1]) - want[-1]).max() > 1e-4
    np.testing.assert_allclose(np.asarray(moved[:-1]), want[:-1], rtol=1e-5,
                               atol=1e-5)


def test_bf16_serving_stays_near_the_reference():
    got, want, _ = _serve_against_reference(CFG)
    assert np.abs(got - want).max() < 0.02 * max(1.0, np.abs(want).max())


def test_the_ssm_state_carried_into_decode_matters():
    """A decode that starts from empty SSM states leaves the reference."""
    cfg = replace(CFG, dtype="float32")
    embed, norm, layers = _weights(cfg)
    params = _params(cfg, embed, norm, layers)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (33,), 0,
                                cfg.vocab_size, jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, caches = M.prefill(params, cfg, tokens[None, :32], cache_len=33)
        empty = jax.tree.map(jnp.zeros_like, caches)
        for c, e in zip(caches["groups"].values(), empty["groups"].values()):
            if "h" in c:
                c["h"] = e["h"]
        logits, _ = M.decode_step(params, cfg, tokens[32:], caches, 32)
        want = _reference(cfg, embed, norm, layers, tokens, 32)[0]
    assert np.abs(np.asarray(logits[0]) - np.asarray(want)).max() > 1e-3


def _moe_input(cfg, n=24):
    return jax.random.normal(jax.random.PRNGKey(2), (1, n, cfg.d_model),
                             jnp.float32)


@pytest.mark.parametrize("shares", [2, 4])
def test_expert_shares_add_up_to_the_uncut_layer(shares):
    """Each share holds n_experts / shares experts and routes over all of
    them; the shares' routed parts, with the shared expert counted once,
    add up to the reference layer with every expert held."""
    cfg = replace(CFG, dtype="float32")
    w = _weights(cfg)[2][0]
    x = _moe_input(cfg)
    n = cfg.n_experts // shares
    total, pairs = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for s in range(shares):
            part = replace(cfg, experts_held=n, expert_offset=s * n)
            ws = dict(w, input_linear=w["input_linear"][s * n:(s + 1) * n],
                      output_linear=w["output_linear"][s * n:(s + 1) * n])
            p = G.layer_params(part, "ssm_moe", ws)["ffn"]
            out, counters = moe_mod.apply_moe_served(p, part, x)
            total = total + out[0] - _shared(part, ws, x[0])
            pairs += int(counters["pairs"])
            np.testing.assert_allclose(
                np.asarray(out[0]), np.asarray(_moe_reference(part, ws, x[0])),
                rtol=1e-5, atol=1e-5)
        whole = _moe_reference(cfg, w, x[0])
        total = total + _shared(cfg, w, x[0])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert pairs == x.shape[1] * cfg.experts_per_token


def _shared(cfg, w, x):
    fs = cfg.shared_expert_ff
    hu = x @ w["shared_input_linear"].astype(jnp.float32).T
    return (jax.nn.silu(hu[:, :fs]) * hu[:, fs:]) \
        @ w["shared_output_linear"].astype(jnp.float32).T


def test_skewed_routing_drops_no_token():
    """Every token picks the same k experts: each of them takes all T
    tokens, far past what the training path's capacity keeps, and the
    served layer still matches the reference."""
    cfg = replace(CFG, dtype="float32")
    w = dict(_weights(cfg)[2][0])
    k, E, d = cfg.experts_per_token, cfg.n_experts, cfg.d_model
    x = jnp.abs(_moe_input(cfg, n=32))
    rank = jnp.arange(E, 0, -1, dtype=jnp.float32)[:, None]  # expert 0 first
    w["router"] = rank * jnp.ones((E, d), jnp.float32) / d
    p = G.layer_params(cfg, "ssm_moe", w)["ffn"]
    with jax.default_matmul_precision("highest"):
        out, counters = moe_mod.apply_moe_served(p, cfg, x)
        want = _moe_reference(cfg, w, x[0])
    assert int(counters["max_load"]) == x.shape[1]
    assert int(counters["pairs"]) == x.shape[1] * k
    assert x.shape[1] > moe_mod.moe_capacity(cfg, x.shape[1])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_unset_multipliers_leave_a_gqa_model_bit_identical():
    """qwen2-0.5b sets none of the new fields: its score scale is still
    1/sqrt(head_dim), and setting that value, or the unit multipliers,
    explicitly changes no bit of its logits, prefilled or decoded."""
    cfg = get_config("qwen2-0.5b", reduced=True)
    assert attn_mod.score_scale(cfg) == 1.0 / math.sqrt(cfg.head_dim)
    explicit = replace(cfg, attention_multiplier=1.0 / math.sqrt(cfg.head_dim),
                       embedding_multiplier=1.0, residual_multiplier=1.0,
                       logits_scaling=1.0)
    params = M.init_params(KEY, cfg)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size, jnp.int32)
    for c in (cfg, explicit):
        lp, caches = M.prefill(params, c, tokens[:, :12], cache_len=16)
        ld, _ = M.decode_step(params, c, tokens[:, 12], caches, 12)
        if c is cfg:
            base = (M.forward(params, c, tokens), lp, ld)
        else:
            for a, b in zip(base, (M.forward(params, c, tokens), lp, ld)):
                assert np.array_equal(np.asarray(a), np.asarray(b))


def test_published_config_and_its_cut():
    full = get_config("granite-4.0-h-small")
    assert full.n_groups == 4 and full.block_pattern.count("attn_moe") == 1
    hf = {"hidden_size": 4096, "num_attention_heads": 32,
          "num_key_value_heads": 8, "intermediate_size": 768,
          "vocab_size": 100352, "tie_word_embeddings": True,
          "position_embedding_type": "nope", "rms_norm_eps": 1e-5,
          "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
          "num_hidden_layers": 10, "num_local_experts": 72,
          "num_experts_per_tok": 10, "shared_intermediate_size": 1536,
          "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_expand": 2,
          "mamba_d_head": 64, "mamba_n_heads": 128, "mamba_chunk_size": 256,
          "embedding_multiplier": 12, "residual_multiplier": 0.22,
          "attention_multiplier": 0.0078125, "logits_scaling": 16}
    cut = G.from_hf(hf, experts_held=18)
    assert cut.block_pattern == full.block_pattern and cut.n_groups == 1
    assert cut.experts_here == 18 and cut.n_experts == 72
    for f in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "experts_per_token", "shared_expert_ff",
              "ssm_state", "ssm_head_dim", "ssm_chunk", "attention_multiplier",
              "embedding_multiplier", "residual_multiplier", "logits_scaling"):
        assert getattr(cut, f) == getattr(full, f), f
    # the chip's share: 3,264,039,552 parameters held
    assert cut.param_count() == 3_264_039_552


def test_concurrent_requests_match_serial_ones():
    """Four threads serving at once, as the engine's partitions do, each
    get what a lone caller gets: one request's programs never see
    another's state."""
    import sys
    import threading

    params = M.init_params(KEY, CFG)
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (16,), 0,
                                  CFG.vocab_size, jnp.int32) for i in range(8)]
    serial = [np.asarray(M.serve_request(params, CFG, p[None], 4)["logits"])
              for p in prompts]
    got = [None] * len(prompts)

    def worker(w):
        for i in range(w, len(prompts), 4):
            got[i] = np.asarray(M.serve_request(params, CFG, prompts[i][None],
                                                4)["logits"])

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(w,)) for w in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    for a, b in zip(serial, got):
        assert np.array_equal(a, b)
