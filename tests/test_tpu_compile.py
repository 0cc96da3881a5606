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


@pytest.mark.parametrize("n,c", [LARGE, SMALL], ids=["26000x8192", "8000x1024"])
def test_pairwise_sq_dists_compiles_for_v5e(one_chip, n, c):
    fn = jax.jit(lambda x, cc: kd_ops.pairwise_sq_dists(x, cc, use_pallas=True))
    compiled = fn.lower(_sds((n, DIM), one_chip), _sds((c, DIM), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_assign_compiles_for_v5e(one_chip):
    n, c = LARGE
    fn = jax.jit(lambda x, cc: kd_ops.assign(x, cc, use_pallas=True))
    compiled = fn.lower(_sds((n, DIM), one_chip), _sds((c, DIM), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    labels, best = compiled.out_info
    assert labels.shape == (n,) and best.shape == (n,)


def test_minibatch_step_compiles_for_v5e_and_fits(one_chip, monkeypatch):
    """The whole model update, as the chip runs it: the kernel is on the
    path and one step fits the chip's HBM.  ``ops`` picks the kernel from
    the default backend, which is the CPU here, so the test steers it."""
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
