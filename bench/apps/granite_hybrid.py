"""Granite 4.0-H Small (granitemoehybrid) serving long documents, as the
harness drives it: what a configuration with ``"app": "granite_hybrid"``
runs.

A message is one request: a prompt of ``prompt_tokens`` token ids.  The
mini-app's user function converts it to a device batch of one and serves
it through the program's per-request function,
``repro.models.model.serve_request``: the prompt prefilled in one program
(Mamba-2 scans through ``kernels/ssd_scan``, GQA, the served MoE), then
``new_tokens`` greedy tokens decoded one program each through the SSM,
conv and KV caches.  The weights are read-only, so the step runs outside
the model lock; it enqueues one request's programs together, so that the
partitions' requests run on the chip one after another and not
interleaved.  Weights are made from the key in the published layout
(Hugging Face names) and loaded into the program's tree
(``configs/granite_4_0_h_small.layer_params``); the check makes them again,
one layer at a time, for the benchmark's own float32 reference
(``bench/lm_reference.py``), teacher-forced on the prompt and the tokens
the program chose, and on the experts it chose at the compared positions,
and compares the prompt's last logits and every decoded token's logits,
and how near each of those expert choices was to the reference's top k.
"""

from __future__ import annotations

import threading
import weakref
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import lm_reference, traffic
from repro.configs import granite_4_0_h_small as granite
from repro.models import model as M

__all__ = ["STEP_MODULE", "KERNEL_NAMES", "control", "FAULTS", "make_pool",
           "size_bytes", "init_state", "warm_up", "convert", "make_step",
           "capture", "check", "model_config", "weight_shape",
           "layer_weights", "global_weights"]

# the decode program's module, one per generated token
STEP_MODULE = "lm_decode_token"
# the Pallas SSD kernel's operations, as the trace names them
KERNEL_NAMES = ("ssd_scan",)
BF16 = jnp.bfloat16


def model_config(cfg: dict):
    """The program's ``ModelConfig`` of this configuration: the router
    scores ``router_experts``; ``num_local_experts`` are held here."""
    hf = dict(cfg, num_local_experts=cfg["router_experts"])
    return granite.from_hf(hf, experts_held=cfg["num_local_experts"],
                           expert_offset=cfg["expert_offset"],
                           dtype=cfg["dtype"], name=cfg["name"])


def make_pool(cfg: dict, seed: int) -> list[np.ndarray]:
    """``pool_messages`` seeded prompts of ``prompt_tokens`` ids uniform
    over the vocabulary."""
    rng = np.random.default_rng(traffic.seed_stream(seed, traffic.STREAM_DATA))
    return [rng.integers(0, cfg["vocab_size"], cfg["prompt_tokens"])
            .astype(np.int32) for _ in range(cfg["pool_messages"])]


def size_bytes(x: np.ndarray) -> int:
    return x.nbytes


# -- weights, in the published layout, from the key -------------------------

def weight_shape(cfg: dict) -> tuple:
    """The hashable sizes the weight maker needs."""
    return tuple((k, cfg[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "intermediate_size", "shared_intermediate_size", "mamba_expand",
        "mamba_d_state", "mamba_d_head", "mamba_d_conv", "num_local_experts",
        "router_experts", "vocab_size"))


@partial(jax.jit, static_argnums=(0, 1))
def layer_weights(shape: tuple, kind: str, key):
    """One layer's bf16 weights: matrices N(0, 1/fan_in), norm scales
    1 + N(0, 0.1), Mamba-2's A_log, dt_bias and D as it initialises them."""
    c = dict(shape)
    d, f, fs = c["hidden_size"], c["intermediate_size"], c["shared_intermediate_size"]
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return (jax.random.normal(next(ks), shape) / np.sqrt(fan_in)).astype(BF16)

    def vec(n, mean, std):
        return (mean + std * jax.random.normal(next(ks), (n,))).astype(BF16)

    w = {"input_layernorm": vec(d, 1.0, 0.1),
         "post_attention_layernorm": vec(d, 1.0, 0.1),
         "router": mat((c["router_experts"], d), d),
         "input_linear": mat((c["num_local_experts"], 2 * f, d), d),
         "output_linear": mat((c["num_local_experts"], d, f), f),
         "shared_input_linear": mat((2 * fs, d), d),
         "shared_output_linear": mat((d, fs), fs)}
    if kind == "mamba":
        di, N = c["mamba_expand"] * d, c["mamba_d_state"]
        nh, ch = di // c["mamba_d_head"], di + 2 * N
        dt = jnp.exp(jax.random.uniform(next(ks), (nh,))
                     * (np.log(0.1) - np.log(0.001)) + np.log(0.001))
        w.update({"in_proj": mat((2 * di + 2 * N + nh, d), d),
                  "conv1d_weight": mat((ch, 1, c["mamba_d_conv"]), c["mamba_d_conv"]),
                  "conv1d_bias": vec(ch, 0.0, 0.1),
                  "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(BF16),
                  "A_log": jnp.log(1.0 + 15.0 * jax.random.uniform(
                      next(ks), (nh,))).astype(BF16),
                  "D": vec(nh, 1.0, 0.1), "norm": vec(di, 1.0, 0.1),
                  "out_proj": mat((d, di), di)})
    else:
        H, KV = c["num_attention_heads"], c["num_key_value_heads"]
        dh = d // H
        w.update({"q_proj": mat((H * dh, d), d), "k_proj": mat((KV * dh, d), d),
                  "v_proj": mat((KV * dh, d), d),
                  "o_proj": mat((d, H * dh), H * dh)})
    return w


@partial(jax.jit, static_argnums=(0,))
def global_weights(shape: tuple, key):
    """The bf16 tied embedding N(0, 0.02) and final norm scale."""
    c = dict(shape)
    k_emb, k_norm = jax.random.split(key)
    d = c["hidden_size"]
    return (0.02 * jax.random.normal(k_emb, (c["vocab_size"], d))).astype(BF16), \
        (1.0 + 0.1 * jax.random.normal(k_norm, (d,))).astype(BF16)


def _keys(key):
    k_global, k_layers = jax.random.split(key)
    return k_global, k_layers


@partial(jax.jit, static_argnums=(0, 1))
def _load_layer(mc, kind, w):
    """One layer's published weights as the program's block (scanned once:
    a leading axis of 1)."""
    return jax.tree.map(lambda a: a[None], granite.layer_params(mc, kind, w))


def init_state(cfg: dict, key: jax.Array, device) -> dict:
    """The program's parameters, loaded one layer at a time so that only
    one layer's published copy is held beside them."""
    mc = model_config(cfg)
    shape = weight_shape(cfg)
    k_global, k_layers = _keys(key)
    groups = {}
    for i, (t, kind) in enumerate(zip(cfg["layer_types"], mc.block_pattern)):
        w = layer_weights(shape, t, jax.random.fold_in(k_layers, i))
        groups[f"b{i}_{kind}"] = _load_layer(mc, kind, w)
        del w
    embed, norm = global_weights(shape, k_global)
    return {"embedding": {"tokens": embed}, "stack": {"groups": groups, "tail": []},
            "final_norm": {"scale": norm}}


def warm_up(cfg: dict, key: jax.Array, device, pool: list, step):
    """Two requests: the prefill, the decode program (on a fresh state and
    on its own output) and whatever the step adds; returns the state the
    step leaves (the control's rounded weights)."""
    params = init_state(cfg, key, device)
    x = convert(pool[0])
    for _ in range(2):
        params, out = step(params, x)
        jax.block_until_ready(out)
    return params


def convert(x: np.ndarray) -> jax.Array:
    """One prompt as a device batch of one."""
    return jnp.asarray(x[None])


def make_step(cfg: dict, program=None):
    """The timed step ``(params, x) -> (params, out)`` around ``program``
    (``serve_request`` or a stand-in with its signature), outside the
    model lock: the weights are read-only.  A request's programs are
    enqueued under a lock of the step's own, so that the partitions'
    requests run one after another and not interleaved: each request is
    done after its own 33 programs and those queued before it, and a
    traced window holds whole requests, decode programs included.  A
    program with a ``prepare`` method has it applied to the weights first
    (the control's rounding)."""
    mc = model_config(cfg)
    n_new = cfg["new_tokens"]
    program = program or M.serve_request
    prepare = getattr(program, "prepare", None)
    enqueue = threading.Lock()

    def step(params, x):
        with enqueue:
            if prepare is not None:
                params = prepare(params)
            return params, program(params, mc, x, n_new)

    return step, False


def capture(state, out):
    """The request's outputs, fresh buffers that nothing later overwrites;
    nothing before a step, as the weights never change."""
    return out


def check(cfg: dict, key: jax.Array, x: np.ndarray, before, after) -> dict:
    """One served request against the reference, teacher-forced on the
    prompt and the tokens the program fed, over the prompt's last logits
    and every decoded token's (33 vectors), at whose positions the
    reference takes the experts the program chose: ``route_err``, the
    largest route gap of those choices over the positions and layers (0
    where each was a top-k choice of the reference's router; a choice of
    another number of experts than k reads that difference, and the
    reference then routes by itself); ``logit_err``, the largest gap ÷
    max(1, largest |reference logit|); ``logit_rms_err``, the gaps' root
    mean square ÷ the reference logits'; ``logit_median_err``, the median
    over the vectors of each one's largest gap ÷ max(1, largest |reference
    logit|).  Those the configuration's ``limits`` name are judged; the
    rest, with the MoE counters, are notes."""
    shape = weight_shape(cfg)
    k_global, k_layers = _keys(key)
    embed, norm = global_weights(shape, k_global)
    tokens = jnp.concatenate([jnp.asarray(x), after["tokens"][0]])
    routes = after["routes"][0]                     # (33, layers, k)
    k = cfg["num_experts_per_tok"]
    forward = partial(
        lm_reference.forward_logits, cfg, embed, norm,
        lambda i: layer_weights(shape, cfg["layer_types"][i],
                                jax.random.fold_in(k_layers, i)),
        tokens, len(x) - 1)
    if routes.shape[-1] == k:
        want, gaps = forward(routes=jnp.moveaxis(routes, 0, 1))
        route_err = max(0.0, float(jnp.max(gaps)))
    else:
        want, route_err = forward(), float(abs(routes.shape[-1] - k))
    got = jnp.concatenate([after["prefill_logits"], after["logits"][0]])
    want, got = np.asarray(want, np.float64), np.asarray(got, np.float64)
    gap = np.abs(got - want)
    scale = max(1.0, np.abs(want).max())
    return {"route_err": route_err,
            "logit_err": float(gap.max() / scale),
            "logit_rms_err": float(np.sqrt(np.mean(gap ** 2))
                                   / np.sqrt(np.mean(want ** 2))),
            "logit_median_err": float(np.median(gap.max(axis=1)) / scale),
            "pairs_here": int(after["moe_pairs"]),
            "max_expert_load": int(after["moe_max_load"])}


# -- the control and the planted faults -------------------------------------

def _fp8(a):
    """``a`` rounded to float8_e4m3's 3 mantissa bits and its exponent
    range, kept in its dtype.  ``reduce_precision`` is never folded away,
    as a convert to float8 and back may be where the compiler allows
    excess precision (on the TPU it is: the control then equals the
    program)."""
    return jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)


@partial(jax.jit, donate_argnums=(0,))
def _round_fp8(params):
    """Every weight matrix rounded to float8_e4m3, in place: the tied
    embedding, and each layer's projections, conv, router and experts
    (a layer's leaves carry a leading axis of scanned groups)."""
    stack = jax.tree.map(lambda a: _fp8(a) if a.ndim > 2 else a,
                         params["stack"])
    return dict(params, stack=stack,
                embedding={"tokens": _fp8(params["embedding"]["tokens"])})


class _Fp8Control:
    """The step with its weights rounded to float8_e4m3, the precision
    just below the configuration's bf16: norms, biases and the SSM's
    scalars stay as they are.  The rounding is done once, in place, on
    the first weights it sees; the step then keeps serving those."""

    def __init__(self):
        self._done = lambda: None

    def prepare(self, params):
        if jax.tree.leaves(params)[0] is not self._done():
            params = _round_fp8(params)
            self._done = weakref.ref(jax.tree.leaves(params)[0])
        return params

    def __call__(self, params, mc, tokens, n_new):
        return M.serve_request(params, mc, tokens, n_new)


control = _Fp8Control()


@jax.jit
def _forget_ssm_state(state):
    groups = {k: (dict(c, h=jnp.zeros_like(c["h"])) if "h" in c else c)
              for k, c in state["caches"]["groups"].items()}
    return dict(state, caches=dict(state["caches"], groups=groups))


def _ssm_state_dropped(params, mc, tokens, n_new):
    prefill_logits, state = M.lm_prefill(params, mc, tokens, n_new)
    state = _forget_ssm_state(state)
    for _ in range(n_new):
        state = M.lm_decode_token(params, mc, state)
    return {"prefill_logits": prefill_logits, "logits": state["logits"],
            "tokens": state["tokens"],
            "moe_pairs": state["counters"]["pairs"],
            "moe_max_load": state["counters"]["max_load"],
            "routes": state["routes"]}


def _routed_expert_dropped(params, mc, tokens, n_new):
    return M.serve_request(params, replace(mc, experts_per_token=
                                           mc.experts_per_token - 1),
                           tokens, n_new)


def _shared_expert_dropped(params, mc, tokens, n_new):
    groups = {k: dict(b, ffn=dict(b["ffn"], shared=dict(
                  b["ffn"]["shared"],
                  w_down=jnp.zeros_like(b["ffn"]["shared"]["w_down"]))))
              for k, b in params["stack"]["groups"].items()}
    return M.serve_request(
        dict(params, stack=dict(params["stack"], groups=groups)), mc, tokens,
        n_new)


# steps the check must judge not correct (bench/plant.py runs them)
FAULTS = {"ssm_state_dropped": _ssm_state_dropped,
          "routed_expert_dropped": _routed_expert_dropped,
          "shared_expert_dropped": _shared_expert_dropped}
