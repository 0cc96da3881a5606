"""``chip_smoke.py`` on the CPU: its K-Means phase at a tiny size (the jnp
path of the kernel), the float64 reference it is checked against, and its
refusal to run without a TPU."""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.pilot.api import PilotComputeService, PilotDescription

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


def test_kmeans_phase_runs_the_main_path_and_matches_the_reference():
    res = chip_smoke.run_kmeans_phase(n_messages=8, points=512, centroids=64,
                                      partitions=4, seed=3)
    assert res["processed"] == 8
    assert res["failed_batches"] == res["abandoned"] == res["retried"] == 0
    assert res["compiles_in_run"] == 0
    assert not res["kernel_in_step"]          # CPU: the jnp reference runs
    assert res["label_agreement_min"] >= chip_smoke.LABEL_AGREEMENT_MIN
    assert res["max_centroid_err_rel"] <= chip_smoke.CENTROID_TOL_REL
    assert len(res["step_s"]) == len(res["convert_s"]) == 8


def test_compile_counter_sees_a_compile():
    import jax
    import jax.numpy as jnp

    before = chip_smoke._compiles
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    assert chip_smoke._compiles > before


def test_reference_replay_flags_wrong_labels_and_wrong_updates():
    rng = np.random.default_rng(0)
    c = rng.normal(size=(16, 9)) * 5
    counts = rng.integers(0, 4, 16).astype(np.float64)
    x = rng.normal(size=(256, 9)).astype(np.float32) * 5
    own, _, _ = chip_smoke._two_nearest(x.astype(np.float64), c)
    after, _ = chip_smoke.reference_step(c, counts, x.astype(np.float64), own)

    def replay(labels, result):
        return chip_smoke.reference_replay([(x, c, counts, labels, result)])

    good = replay(own, after)
    assert good["agreement"] == [1.0] and good["centroid_err_rel"] < 1e-12
    assert replay((own + 1) % 16, after)["agreement"][0] \
        < chip_smoke.LABEL_AGREEMENT_MIN
    assert replay(own, after * (1 + 1e-3))["centroid_err_rel"] \
        > chip_smoke.CENTROID_TOL_REL


def test_a_failed_unit_is_raised_not_counted():
    pcs = PilotComputeService()
    pilot = pcs.submit_pilot(PilotDescription(
        resource="jax://mesh", attrs={"mesh_shape": (1,), "mesh_axes": ("data",)}))

    def boom():
        raise FloatingPointError("device step failed")

    pilot.submit_compute_unit(func=boom)
    with pytest.raises(FloatingPointError, match="device step failed"):
        chip_smoke._raise_failed_unit(pilot)


def test_main_refuses_a_host_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
