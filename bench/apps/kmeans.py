"""The paper's streaming K-Means mini-app (arXiv 1909.06055 §IV) as the
harness drives it: what a configuration with ``"app": "kmeans"`` runs.

A message is ``points_per_message`` clustered float32 points of ``dim``
coordinates.  The mini-app's user function converts it with
``jnp.asarray`` and applies ``kmeans.minibatch_step`` to the shared model
(centroids and counts) under the model lock: ``full_fit_locked`` sharing.
The step returns nothing else: the model is its only output.  The check
copies the model before and after a step and judges the step with the
float64 reference of ``bench/reference.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic
from bench.control import control_step as control
from repro.models import kmeans

__all__ = ["STEP_MODULE", "KERNEL_NAMES", "control", "make_pool",
           "size_bytes", "init_state", "warm_up", "convert", "make_step",
           "capture", "check"]

STEP_MODULE = "minibatch_step"
# the kmeans_distance kernels' operations, as the trace names them
KERNEL_NAMES = ("pairwise_sq_dists_pallas", "assign_pallas")
convert = jnp.asarray
_init = jax.jit(kmeans.init_state, static_argnums=(1, 2, 3))


def make_pool(cfg: dict, seed: int) -> list[np.ndarray]:
    """``cfg["pool_messages"]`` seeded clustered messages of
    ``points_per_message`` float32 points: blob centres uniform in
    ±``scale``, unit Gaussian noise around them."""
    data = cfg["data"]
    n, d, p = cfg["points_per_message"], cfg["dim"], cfg["pool_messages"]
    rng = np.random.default_rng(traffic.seed_stream(seed, traffic.STREAM_DATA))
    centres = rng.uniform(-data["scale"], data["scale"], (data["blobs"], d))
    out = []
    for _ in range(p):
        which = rng.integers(0, data["blobs"], n)
        pts = centres[which] + data["noise"] * rng.normal(size=(n, d))
        out.append(pts.astype(np.float32))
    return out


def size_bytes(x: np.ndarray) -> int:
    return x.nbytes


def init_state(cfg: dict, key: jax.Array, device) -> kmeans.KMeansState:
    """The young model: ``centroids`` Gaussian centroids of scale
    ``init_scale`` from ``key``, counts zero, on ``device``."""
    return jax.device_put(_init(key, cfg["centroids"], cfg["dim"],
                                cfg["data"]["init_scale"]), device)


def warm_up(cfg: dict, key: jax.Array, device, pool: list, step):
    """Compiles what the timed path runs (the check's copy, and the step
    on a fresh state and on a step's own output), then returns a fresh
    state."""
    warm = init_state(cfg, key, device)
    pts0 = convert(pool[0])
    for _ in range(2):
        capture(warm, None)
        warm, _ = step(warm, pts0)
    jax.block_until_ready(warm)
    state = init_state(cfg, key, device)
    jax.block_until_ready(state)
    return state


def make_step(cfg: dict, program=None):
    """The timed step ``(state, x) -> (state, None)`` around ``program``
    (``kmeans.minibatch_step``, or a stand-in for it), and whether it runs
    under the model lock: it does, the one sharing policy driven here."""
    if cfg["sharing"] != "full_fit_locked":
        raise ValueError(f"sharing {cfg['sharing']!r}: only full_fit_locked "
                         f"is driven by this app")
    program = program or kmeans.minibatch_step

    def step(state, x):
        return program(state, x), None

    return step, True


@jax.jit
def _copy_state(state):
    return jax.tree.map(jnp.copy, state)


def capture(state, out):
    """The model, copied on the device: before a checked step and after
    it (the step donates the state it is given)."""
    return _copy_state(state)


def check(cfg: dict, key: jax.Array, x: np.ndarray, before, after) -> dict:
    """One copied step against the reference: ``count_err`` and
    ``centroid_err``, judged by the configuration's limits, and the
    points ``near_ties`` left out of them."""
    host = [tuple(np.asarray(a) for a in (s.centroids, s.counts))
            for s in (before, after)]
    r = reference.check_step(x, *host)
    return {k: r[k] for k in ("count_err", "centroid_err", "near_ties")}
