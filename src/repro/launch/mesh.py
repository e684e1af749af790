"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches JAX device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any JAX
import, and tests/benches must keep seeing 1 device.

Topology (TPU v5e pods):
  single-pod : (data=16, model=16)            = 256 chips
  multi-pod  : (pod=2, data=16, model=16)     = 512 chips; the ``pod`` axis
               is pure data parallelism over DCN.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis of the Auto type."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (host) devices the test session has."""
    return make_mesh((data, model), ("data", "model"))


def mesh_num_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
