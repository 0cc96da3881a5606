"""Tests for the pilot abstraction and its backend plugins."""

import numpy as np
import pytest

from repro.pilot.api import (ComputeUnitDescription, PilotComputeService,
                             PilotDescription, State, TaskProfile)


def make_service(**kw):
    return PilotComputeService(**kw)


# -- local backend (real execution) ------------------------------------------

def test_local_backend_executes_real_function():
    pcs = make_service()
    pilot = pcs.submit_pilot(PilotDescription(resource="local://", concurrency=2))
    cu = pilot.submit_compute_unit(func=lambda a, b: a + b, args=(2, 3))
    assert cu.result(timeout=10) == 5
    assert cu.state == State.DONE
    pcs.close()


def test_local_backend_failure_propagates():
    pcs = make_service()
    pilot = pcs.submit_pilot(PilotDescription(resource="local://"))

    def boom():
        raise ValueError("boom")

    cu = pilot.submit_compute_unit(func=boom)
    with pytest.raises(ValueError, match="boom"):
        cu.result(timeout=10)
    assert cu.state == State.FAILED
    pcs.close()


def test_local_backend_parallel_tasks():
    pcs = make_service()
    pilot = pcs.submit_pilot(PilotDescription(resource="local://", concurrency=4))
    cus = [pilot.submit_compute_unit(func=lambda i=i: i * i) for i in range(8)]
    assert [cu.result(timeout=10) for cu in cus] == [i * i for i in range(8)]
    pcs.close()


# -- serverless sim backend ---------------------------------------------------

PROFILE = TaskProfile(flops=2e9, read_bytes=4e4, write_bytes=4e4, msg_bytes=3e5)


def run_one(memory_mb, profile=PROFILE, **pilot_kw):
    pcs = make_service(seed=1)
    pilot = pcs.submit_pilot(PilotDescription(
        resource="serverless://aws-sim", memory_mb=memory_mb, partitions=1, **pilot_kw))
    cu = pilot.submit_compute_unit(ComputeUnitDescription(profile=profile))
    cu.wait()
    return cu


def test_lambda_memory_scales_cpu():
    """Paper Fig 3: larger containers -> shorter runtimes (CPU prop. to mem)."""
    runtimes = [run_one(m).runtime for m in [256, 512, 1024, 2048, 3008]]
    assert all(np.diff(runtimes) < 0), runtimes
    # scaling is roughly 1/memory for the compute-bound part
    assert runtimes[0] / runtimes[-1] > 5


def test_lambda_memory_cap_3008():
    """Memory above the 2019 cap gives no extra CPU."""
    r1 = run_one(3008).runtime
    r2 = run_one(10000).runtime
    assert r2 == pytest.approx(r1, rel=0.15)


def test_lambda_walltime_kill():
    cu = run_one(128, profile=TaskProfile(flops=1e13))  # hours at 128MB
    assert cu.state == State.FAILED
    assert isinstance(cu.exception, TimeoutError)


def test_lambda_oom():
    cu = run_one(512, profile=TaskProfile(flops=1.0, memory_mb=4096))
    assert cu.state == State.FAILED
    assert isinstance(cu.exception, MemoryError)


def test_lambda_concurrency_cap_30():
    """Paper: at most 30 concurrent containers even with more partitions."""
    pcs = make_service(seed=0)
    pilot = pcs.submit_pilot(PilotDescription(
        resource="serverless://aws-sim", memory_mb=3008, partitions=64))
    backend = pilot.backend
    assert len(backend._pilots[pilot.uid]["containers"]) == 30


def test_lambda_cold_start_once_per_container():
    pcs = make_service(seed=2)
    pilot = pcs.submit_pilot(PilotDescription(
        resource="serverless://aws-sim", memory_mb=3008, partitions=1,
        attrs={"jitter_cv_ref": 0.0}))
    p = TaskProfile(flops=1e9)
    cu1 = pilot.submit_compute_unit(ComputeUnitDescription(profile=p))
    cu1.wait()
    cu2 = pilot.submit_compute_unit(ComputeUnitDescription(profile=p))
    cu2.wait()
    assert cu1.attrs["cold"] and not cu2.attrs["cold"]
    assert cu1.runtime > cu2.runtime  # cold start penalty


def test_serverless_executes_real_function_too():
    pcs = make_service(seed=0)
    pilot = pcs.submit_pilot(PilotDescription(resource="serverless://aws-sim"))
    cu = pilot.submit_compute_unit(ComputeUnitDescription(
        func=lambda: 42, profile=TaskProfile(flops=1e6)))
    assert cu.result() == 42


# -- hpc sim backend ----------------------------------------------------------

def test_hpc_lock_serializes_serial_flops():
    """Tasks whose work is all serial_flops cannot run concurrently."""
    pcs = make_service(seed=0)
    pilot = pcs.submit_pilot(PilotDescription(
        resource="hpc://wrangler-sim", partitions=4, attrs={"jitter_cv": 0.0}))
    prof = TaskProfile(serial_flops=5.2e9)  # exactly 1s of locked work
    cus = [pilot.submit_compute_unit(ComputeUnitDescription(profile=prof))
           for _ in range(4)]
    pilot.wait_all()
    end_times = sorted(cu.end_ts for cu in cus)
    # lock forces ~1s spacing despite 4 workers
    gaps = np.diff(end_times)
    assert np.all(gaps > 0.9), gaps


def test_hpc_parallel_flops_scale():
    """Pure-parallel tasks finish ~concurrently on distinct workers."""
    pcs = make_service(seed=0)
    pilot = pcs.submit_pilot(PilotDescription(
        resource="hpc://wrangler-sim", partitions=4, attrs={"jitter_cv": 0.0}))
    prof = TaskProfile(flops=3.6e9)
    cus = [pilot.submit_compute_unit(ComputeUnitDescription(profile=prof))
           for _ in range(4)]
    pilot.wait_all()
    end_times = [cu.end_ts for cu in cus]
    assert max(end_times) - min(end_times) < 0.2, end_times


def test_hpc_stampede2_slower_cores():
    def run(machine):
        pcs = make_service(seed=0)
        pilot = pcs.submit_pilot(PilotDescription(
            resource=f"hpc://{machine}-sim", partitions=1, attrs={"jitter_cv": 0.0}))
        cu = pilot.submit_compute_unit(ComputeUnitDescription(
            profile=TaskProfile(flops=1e10)))
        cu.wait()
        return cu.runtime

    assert run("stampede2") > run("wrangler")


def test_hpc_kill_worker_fails_running_cu():
    pcs = make_service(seed=0)
    pilot = pcs.submit_pilot(PilotDescription(
        resource="hpc://wrangler-sim", partitions=2, attrs={"jitter_cv": 0.0}))
    backend = pilot.backend
    prof = TaskProfile(flops=3.6e10)  # 10s
    cu = pilot.submit_compute_unit(ComputeUnitDescription(profile=prof, partition=0))
    backend.sim.run_until(t=1.0)
    assert cu.state == State.RUNNING
    backend.kill_worker(pilot, cu.attrs["worker"])
    assert cu.state == State.FAILED
    assert isinstance(cu.exception, ConnectionError)


def test_unknown_machine_rejected():
    pcs = make_service()
    with pytest.raises(ValueError, match="unknown HPC machine"):
        pcs.submit_pilot(PilotDescription(resource="hpc://frontier-sim"))


def test_shared_resource_public_accessor():
    """backend.shared_resource(pilot, name) replaces reaching into
    backend._pilots[...]: HPC exposes the Lustre resource and the model
    lock; isolated backends raise LookupError."""
    from repro.sim.des import SharedResource, SimLock

    pcs = make_service()
    hpc = pcs.submit_pilot(PilotDescription(resource="hpc://wrangler-sim",
                                            partitions=2))
    assert isinstance(hpc.backend.shared_resource(hpc, "fs"), SharedResource)
    assert isinstance(hpc.backend.shared_resource(hpc, "model_lock"), SimLock)
    with pytest.raises(LookupError):
        hpc.backend.shared_resource(hpc, "gpfs")

    sls = pcs.submit_pilot(PilotDescription(resource="serverless://aws-sim",
                                            partitions=2))
    with pytest.raises(LookupError):
        sls.backend.shared_resource(sls, "fs")


# -- jaxmesh backend -------------------------------------------------------------

def test_jaxmesh_pilot_runs_under_mesh():
    import jax
    import jax.numpy as jnp

    pcs = make_service()
    pilot = pcs.submit_pilot(PilotDescription(
        resource="jax://mesh", attrs={"mesh_shape": (1,), "mesh_axes": ("data",)}))
    assert pilot.mesh.shape == {"data": 1}

    def fn():
        return float(jnp.sum(jnp.ones((4, 4))))

    cu = pilot.submit_compute_unit(func=fn)
    assert cu.result(timeout=30) == 16.0


def test_jaxmesh_overallocation_rejected():
    pcs = make_service()
    with pytest.raises(RuntimeError, match="devices"):
        pcs.submit_pilot(PilotDescription(
            resource="jax://mesh", attrs={"mesh_shape": (1000,), "mesh_axes": ("data",)}))


class _LateDeviceError:
    """An output whose error surfaces only when it is waited on, as an
    error on an asynchronously dispatched device computation does."""

    def block_until_ready(self):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory on the device")


@pytest.mark.parametrize("fails", ["in_call", "on_device"])
def test_jaxmesh_failed_unit_carries_its_exception(fails):
    pcs = make_service()
    pilot = pcs.submit_pilot(PilotDescription(
        resource="jax://mesh", attrs={"mesh_shape": (1,), "mesh_axes": ("data",)}))

    def fn():
        if fails == "in_call":
            raise ValueError("bad input")
        return _LateDeviceError()

    cu = pilot.submit_compute_unit(func=fn)
    assert cu.state == State.FAILED
    expected = ValueError if fails == "in_call" else RuntimeError
    assert isinstance(cu.exception, expected)
    with pytest.raises(expected):
        cu.result(timeout=30)
