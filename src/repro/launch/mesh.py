"""Mesh construction: every mesh in the repo is built here.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.

Axes are ``Auto``: the sharding rules annotate activations with
``with_sharding_constraint`` and leave propagation to the compiler, which
jax only accepts on Auto axes (``jax.make_mesh`` defaults to Explicit).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """A mesh of ``shape`` over ``axes`` (all Auto), on ``devices`` if given,
    else on the first ``prod(shape)`` of ``jax.devices()``."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod
    axis shards the global batch across pods (DCI traffic: gradient
    all-reduce only)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
