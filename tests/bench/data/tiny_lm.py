"""A throwaway app for ``tests/bench/test_bench_harness.py``, copied into
a scratch root as ``bench/apps/tiny_lm.py``: a one-layer token model
served request by request, shaped like a language model's request stream
rather than like K-Means.

Its weights are read-only and made from the key; a message is a prompt of
token ids, of one of ``prompt_lengths``; the step returns the prompt's
logits and runs outside the model lock; the check compares them with a
float64 numpy forward pass.
"""

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic

STEP_MODULE = "forward"
KERNEL_NAMES = ()
control = None
convert = jnp.asarray


def make_pool(cfg, seed):
    rng = np.random.default_rng(traffic.seed_stream(seed, traffic.STREAM_DATA))
    lengths = cfg["prompt_lengths"]
    return [rng.integers(0, cfg["vocab"], lengths[j % len(lengths)])
            .astype(np.int32) for j in range(cfg["pool_messages"])]


def size_bytes(x):
    return x.nbytes


def _weights(cfg, key):
    rng = np.random.default_rng(np.asarray(key))
    v, d = cfg["vocab"], cfg["width"]
    return {"embed": rng.normal(size=(v, d)),
            "unembed": rng.normal(size=(d, v)) / np.sqrt(d)}


def init_state(cfg, key, device):
    return jax.device_put({k: w.astype(np.float32)
                           for k, w in _weights(cfg, key).items()}, device)


@jax.jit
def forward(weights, tokens):
    h = jnp.tanh(weights["embed"][tokens])
    return jnp.matmul(h, weights["unembed"],
                      precision=jax.lax.Precision.HIGHEST)


def make_step(cfg, program=None):
    program = program or forward

    def step(weights, x):
        return weights, program(weights, x)

    return step, False


def warm_up(cfg, key, device, pool, step):
    """One step at each prompt length: every shape the window runs."""
    weights = init_state(cfg, key, device)
    for n in sorted({len(x) for x in pool}):
        x = next(x for x in pool if len(x) == n)
        jax.block_until_ready(capture(*step(weights, convert(x))))
    return weights


def capture(state, out):
    """The logits; nothing before a step, as the weights never change."""
    return out


def check(cfg, key, x, before, after):
    w = _weights(cfg, key)
    want = np.tanh(w["embed"][x]) @ w["unembed"]
    gap = np.abs(np.asarray(after, np.float64) - want).max()
    return {"logit_err": float(gap / max(1.0, np.abs(want).max())),
            "tokens": len(x)}
