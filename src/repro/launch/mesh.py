"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before its
first jax import, and everything else must see the real device count.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the sharding rules constrain
    activations with ``with_sharding_constraint``, which only names
    Auto axes (``jax.make_mesh`` defaults to Explicit ones)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None):
    """Tiny mesh over whatever devices exist (CPU tests)."""
    n = n_devices or len(jax.devices())
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return _auto_mesh((n // model, model), ("data", "model"))
