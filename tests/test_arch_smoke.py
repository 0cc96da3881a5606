"""Per-architecture smoke tests: reduced config, one forward + train-grad +
prefill/decode consistency on CPU.  Asserts shapes and finiteness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, list_configs
from repro.models import model as M

ARCHS = list_configs()
B, S = 2, 32


def make_batch(cfg, key, batch=B, seq=S):
    kt, ke = jax.random.split(key)
    tokens = jax.random.randint(kt, (batch, seq), 0, cfg.vocab_size, jnp.int32)
    batch_d = {"tokens": tokens}
    if cfg.frontend is not None:
        batch_d["embeds"] = 0.02 * jax.random.normal(
            ke, (batch, cfg.n_prefix, cfg.d_model), jnp.float32)
    return batch_d


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finiteness(arch, rng):
    cfg = get_config(arch, reduced=True)
    params = M.init_params(rng, cfg)
    batch = make_batch(cfg, rng)
    logits = M.forward(params, cfg, batch["tokens"], batch.get("embeds"))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_loss_and_grads_finite(arch, rng):
    cfg = get_config(arch, reduced=True)
    params = M.init_params(rng, cfg)
    batch = make_batch(cfg, rng)
    loss, grads = jax.value_and_grad(M.loss_fn)(params, cfg, batch)
    assert bool(jnp.isfinite(loss)), f"{arch}: loss {loss}"
    # a random model over V tokens should sit near log(V)
    assert 0.1 * np.log(cfg.vocab_size) < float(loss) < 3.0 * np.log(cfg.vocab_size)
    leaves = jax.tree.leaves(grads)
    assert leaves, "no grads"
    for g in leaves:
        assert bool(jnp.isfinite(g).all()), f"{arch}: non-finite grad"
    nonzero = sum(float(jnp.sum(jnp.abs(g))) > 0 for g in leaves)
    assert nonzero > len(leaves) // 2, f"{arch}: too many zero grads"


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch, rng):
    """Prefill+decode logits must match full-sequence forward (the KV-cache /
    recurrent-state path is exact, not an approximation)."""
    cfg = get_config(arch, reduced=True)
    params = M.init_params(rng, cfg)
    batch = make_batch(cfg, rng)
    tokens = batch["tokens"]
    full = M.forward(params, cfg, tokens, batch.get("embeds"))

    n_prompt = S // 2
    logits_p, caches = M.prefill(params, cfg, tokens[:, :n_prompt], cache_len=S,
                                 embeds=batch.get("embeds"))
    np.testing.assert_allclose(np.asarray(logits_p),
                               np.asarray(full[:, n_prompt - 1]),
                               rtol=2e-2, atol=2e-2)
    # decode the next tokens one by one, teacher-forced
    logits_d = logits_p
    for i in range(n_prompt, min(n_prompt + 4, S)):
        logits_d, caches = M.decode_step(params, cfg, tokens[:, i], caches, i)
        np.testing.assert_allclose(np.asarray(logits_d), np.asarray(full[:, i]),
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "recurrentgemma-2b",
                                  "mamba2-130m", "granite-moe-3b-a800m"])
def test_greedy_generate_runs(arch, rng):
    cfg = get_config(arch, reduced=True)
    params = M.init_params(rng, cfg)
    prompt = jax.random.randint(rng, (1, 8), 0, cfg.vocab_size, jnp.int32)
    out = M.serve_request(params, cfg, prompt, n_new=4)["tokens"]
    assert out.shape == (1, 4)
    assert bool((out >= 0).all()) and bool((out < cfg.vocab_size).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_structure_matches(arch, rng):
    """The sharding-spec tree must mirror the param tree exactly."""
    cfg = get_config(arch, reduced=True)
    params = M.init_params(rng, cfg)
    specs = M.param_specs(cfg)
    pstruct = jax.tree.structure(params)
    sstruct = jax.tree.structure(specs, is_leaf=lambda s: isinstance(s, tuple))
    assert pstruct == sstruct, f"{arch}:\n{pstruct}\nvs\n{sstruct}"
    # every spec entry has the right rank
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, tuple))
    for a, s in zip(flat_p, flat_s):
        assert a.ndim == len(s), f"{arch}: param rank {a.shape} vs spec {s}"


@pytest.mark.parametrize("arch", ARCHS)
def test_cache_specs_structure_matches(arch, rng):
    cfg = get_config(arch, reduced=True)
    caches = M.cache_init(cfg, B, 16)
    specs = M.cache_specs(cfg)
    cstruct = jax.tree.structure(caches)
    sstruct = jax.tree.structure(specs, is_leaf=lambda s: isinstance(s, tuple))
    assert cstruct == sstruct
    for a, s in zip(jax.tree.leaves(caches),
                    jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, tuple))):
        assert a.ndim == len(s), f"{arch}: cache rank {a.shape} vs spec {s}"
