"""Production mesh construction (function, not module constant — importing
this module never touches jax device state).

Meshes are built with ``AxisType.Auto`` axes: the sharding helpers
(``parallel.ctx.shard_activation`` / ``with_sharding_constraint``) place
activations by constraint, which explicit-typed axes reject."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape))


def make_host_mesh(model: int = 1):
    """Tiny ``("data", "model")`` mesh over locally-available devices
    (tests / CPU smoke runs, e.g. under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

    ``model`` is the size of the model (tensor-parallel) axis; the data axis
    takes the rest.  Example::

        mesh = make_host_mesh(model=4)   # 8 devices -> (2, 4) data x model
    """
    n = jax.device_count()
    if model < 1:
        raise ValueError(f"make_host_mesh: model={model} must be >= 1")
    if n % model != 0:
        raise ValueError(
            f"make_host_mesh: model={model} does not divide the "
            f"{n} available device(s) "
            f"({[d.platform for d in jax.devices()[:4]]}...); pick a model-"
            "axis size that divides jax.device_count() — on CPU, force more "
            "devices with XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return jax.make_mesh((n // model, model), ("data", "model"),
                         (AxisType.Auto, AxisType.Auto))
