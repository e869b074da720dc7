"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state -- the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init and
only then calls this.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_host_mesh(p: int, axis: str = "data"):
    """Small host-device mesh for tests/benchmarks."""
    import numpy as np

    return jax.sharding.Mesh(np.array(jax.devices()[:p]), (axis,))
