"""Production meshes.  A function, not a constant: importing this module
must never touch jax device state (the dry-run sets XLA_FLAGS first)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math

    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) == n:
        return jax.make_mesh(shape, axes, axis_types=_auto(axes))
    # dry-run: 512 host devices present; single-pod mesh uses the first 256
    import numpy as np

    return jax.sharding.Mesh(np.asarray(devs[:n]).reshape(shape), axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the real local devices (tests/examples on CPU)."""
    n = len(jax.devices())
    model = min(model, n)
    axes = ("data", "model")
    return jax.make_mesh((n // model, model), axes, axis_types=_auto(axes))


def _auto(axes):
    # make_mesh builds Explicit axes by default; ShardCtx places activations
    # with with_sharding_constraint, which only refers to Auto axes
    return (jax.sharding.AxisType.Auto,) * len(axes)
