"""Production mesh definitions (MULTI-POD DRY-RUN step 1).

A function, not a module constant: importing this module never touches jax
device state.  Production target: TPU v5e, 16x16 = 256 chips per pod;
multi-pod adds a leading 'pod' axis (2 pods = 512 chips).
"""
from __future__ import annotations

import jax


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes: the engines and the logical
    sharding rules place data with ``shard_map`` and
    ``with_sharding_constraint``, which refuse ``Explicit`` axes — the
    default of ``jax.make_mesh``."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


# TPU v5e hardware constants (per chip) — §Roofline sources
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW_PER_LINK = 50e9            # bytes/s per link


def mesh_chips(mesh) -> int:
    return mesh.devices.size
