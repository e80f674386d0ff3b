"""Production mesh factory.

Defined as a function (never a module-level constant) so importing this
module never touches jax device state.  The dry-run forces 512 host
platform devices before the first jax import; everything else sees the
real device count.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(shape=None, axes=None):
    """Small mesh over whatever devices exist (tests, local runs)."""
    n = len(jax.devices())
    if shape is None:
        shape, axes = (1, n), ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
