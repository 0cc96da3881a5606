"""JAX mesh backend: pilots are *mesh-slice resource containers*.

TPU-native analogue of the paper's resource containers (DESIGN.md §2): a
pilot owns a contiguous slice of the available jax devices, exposed as a
``jax.sharding.Mesh`` whose shape/axes come from the PilotDescription.
Compute-units are jitted callables executed with the pilot's mesh installed;
elastic scaling = releasing the pilot and re-slicing.

On this CPU host there is a single device, so pilots degrade to a 1×1 mesh —
the full 256/512-chip meshes are exercised by ``launch/dryrun.py`` via
``ShapeDtypeStruct`` lowering (no allocation), per the assignment.
"""

from __future__ import annotations

import threading
import time

import jax
from jax.sharding import Mesh

from repro.pilot.api import Backend, ComputeUnit, Pilot, State, register_backend


class JaxMeshBackend(Backend):
    scheme = "jax"

    def __init__(self, devices=None, **_kw) -> None:
        self.devices = list(devices if devices is not None else jax.devices())
        self._allocated: dict[int, list] = {}
        self._cv = threading.Condition()

    # -- device accounting ----------------------------------------------------
    def _free_devices(self) -> list:
        used = {id(d) for devs in self._allocated.values() for d in devs}
        return [d for d in self.devices if id(d) not in used]

    def start_pilot(self, pilot: Pilot) -> None:
        import numpy as np

        shape = tuple(pilot.desc.attrs.get("mesh_shape", (1,)))
        axes = tuple(pilot.desc.attrs.get("mesh_axes", ("data",)))
        if len(shape) != len(axes):
            raise ValueError(f"mesh_shape {shape} / mesh_axes {axes} mismatch")
        n = int(np.prod(shape))
        free = self._free_devices()
        if n > len(free):
            raise RuntimeError(
                f"pilot wants {n} devices, only {len(free)} free of {len(self.devices)}")
        devs = free[:n]
        self._allocated[pilot.uid] = devs
        pilot.mesh = Mesh(np.asarray(devs, dtype=object).reshape(shape), axes)
        pilot.state = State.RUNNING

    def cancel_pilot(self, pilot: Pilot) -> None:
        self._allocated.pop(pilot.uid, None)
        now = time.perf_counter()
        for cu in pilot.compute_units:
            if not cu.state.is_final:
                cu._set_canceled(now)

    # -- execution: run under the pilot's mesh ---------------------------------
    def submit(self, pilot: Pilot, cu: ComputeUnit) -> None:
        cu.submit_ts = time.perf_counter()
        cu._set_running(time.perf_counter())
        try:
            with pilot.mesh:
                out = cu.desc.func(*cu.desc.args, **cu.desc.kwargs) if cu.desc.func else None
                # dispatch is asynchronous: wait for the device, so that a
                # device error fails this unit and end_ts is the device's end
                jax.block_until_ready(out)
            cu._set_done(time.perf_counter(), out)
        except BaseException as exc:  # noqa: BLE001
            cu._set_failed(time.perf_counter(), exc)
        with self._cv:
            self._cv.notify_all()

    def drive_until(self, predicate, timeout) -> None:
        deadline = None if timeout is None else time.perf_counter() + timeout
        with self._cv:
            while not predicate():
                remaining = None if deadline is None else deadline - time.perf_counter()
                if remaining is not None and remaining <= 0:
                    raise TimeoutError("jaxmesh drive_until timed out")
                self._cv.wait(timeout=remaining if remaining is not None else 0.1)


register_backend("jax", JaxMeshBackend)
