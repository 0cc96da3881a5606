"""The K-Means main path compiles for a TPU v5e chip, at the paper's sizes.

Nothing runs: the chip's compiler, which is installed without the chip,
compiles for a described ``v5e:2x2`` topology and refuses what the chip
would refuse (tiling, VMEM, device memory).  Each test asserts that the
Pallas kernel (``tpu_custom_call``) is in the compiled program.

The topology is described only inside a fixture: one process at a time may
load the TPU library, so describing it at import would fail in every other
test worker.  Keep these tests in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kmeans_distance import ops as kd_ops
from repro.models import kmeans

DIM = 9
# the paper's message and model sizes (arXiv 1909.06055 §IV)
LARGE = (26_000, 8_192)
SMALL = (8_000, 1_024)
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n,c,d,lanes", [(*LARGE, DIM, 128), (*SMALL, DIM, 128),
                                         (*LARGE, 130, 1280)],
                         ids=["26000x8192x9", "8000x1024x9", "26000x8192x130"])
def test_fused_assign_compiles_for_v5e(one_chip, n, c, d, lanes):
    """The kernel keeps the name the benchmark's trace finds it by, and
    contracts the packed bf16 operands, ceil(9 d / 128) lane tiles wide."""
    fn = jax.jit(lambda x, cc: kd_ops.assign(x, cc, use_pallas=True))
    compiled = fn.lower(_sds((n, d), one_chip), _sds((c, d), one_chip)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    name = calls[0].split("=", 1)[0]
    assert "assign_pallas" in name
    operands = calls[0].split("operand_layout_constraints=", 1)[1]
    assert re.findall(r"(\w+)\[\d+,(\d+)\]", operands)[:2] \
        == [("bf16", str(lanes))] * 2
    labels, best = compiled.out_info
    assert labels.shape == (n,) and best.shape == (n,)


def test_minibatch_step_compiles_for_v5e_and_fits(one_chip, monkeypatch):
    """The whole model update, as the chip runs it: the kernel is on the
    path, one step fits the chip's HBM, and its temporaries hold no (n, c)
    distance matrix.  ``ops`` picks the kernel from the default backend,
    which is the CPU here, so the test steers it."""
    monkeypatch.setattr(kd_ops, "_on_tpu", lambda: True)
    n, c = LARGE
    state = kmeans.KMeansState(centroids=_sds((c, DIM), one_chip),
                               counts=_sds((c,), one_chip))
    compiled = kmeans.minibatch_step.lower(state, _sds((n, DIM), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES
    assert mem.temp_size_in_bytes < n * c * 4


def test_ssd_scan_kernel_compiles_for_v5e(one_chip):
    """The Mamba-2 prefill's SSD kernel at granite-4.0-h-small's widths:
    128 heads of 64, state 128, an 8,192-token prompt, chunks of 256."""
    from repro.kernels.ssd_scan import ops as ssd_ops

    S, H, P, N = 8192, 128, 64, 128
    fn = jax.jit(lambda x, dt, A, b, c: ssd_ops.ssd_scan(
        x, dt, A, b, c, chunk=256, use_pallas=True))
    compiled = fn.lower(_sds((1, S, H, P), one_chip), _sds((1, S, H), one_chip),
                        _sds((H,), one_chip), _sds((1, S, N), one_chip),
                        _sds((1, S, N), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    y, h = compiled.out_info
    assert y.shape == (1, S, H, P) and h.shape == (1, H, P, N)


def test_served_moe_layer_compiles_for_v5e(one_chip):
    """One served MoE layer of the benchmark's cut, over an 8,192-token
    prompt: 18 held experts of 72, top 10, the shared expert; the grouped
    matmul is the chip's own kernel, and the layer's temporaries fit."""
    from dataclasses import replace

    from repro.configs.base import get_config
    from repro.models import moe as moe_mod

    cfg = replace(get_config("granite-4.0-h-small"), experts_held=18)
    shapes = jax.eval_shape(
        lambda k: moe_mod.served_moe_init(k, cfg, jnp.bfloat16),
        jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _sds(a.shape, one_chip, a.dtype), shapes)
    x = _sds((1, 8192, cfg.d_model), one_chip, jnp.bfloat16)
    compiled = jax.jit(lambda p, x: moe_mod.apply_moe_served(p, cfg, x)) \
        .lower(params, x).compile()
    assert "ragged-dot" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 * 2**30
