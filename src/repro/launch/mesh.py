"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state.  The production target is TPU v5e:
  * single pod : 16 x 16  = 256 chips, axes ('data', 'model')
  * multi-pod  : 2 x 16 x 16 = 512 chips, axes ('pod', 'data', 'model')
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / reduced dry-runs)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_from_placement(placement, *, devices=None):
    """Build the device mesh a ``repro.api.PlacementSpec`` names.

    Uses the first ``placement.n_devices`` of ``devices`` (default: all
    local devices) so a placement smaller than the machine still works —
    e.g. loading a 2-shard snapshot on a 4-device host.  Raises with the
    ``--xla_force_host_platform_device_count`` hint when the machine has
    too few devices, since that is the usual CPU-test fix.
    """
    import numpy as np
    from jax.sharding import Mesh

    devices = list(jax.devices() if devices is None else devices)
    need = placement.n_devices
    if len(devices) < need:
        raise ValueError(
            f"placement {placement.mesh_shape} over {placement.mesh_axes} "
            f"needs {need} devices but only {len(devices)} are available; "
            f"shrink the placement or force a host-device mesh with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    grid = np.array(devices[:need]).reshape(placement.mesh_shape)
    return Mesh(grid, placement.mesh_axes)


# TPU v5e hardware constants (roofline targets; Google Cloud "TPU v5e" docs)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (intra-pod)
HBM_BYTES = 16 * 1024 ** 3      # 16 GiB per chip
