"""Production mesh construction (TPU v5e target).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — jax locks the
device count on first initialization, and the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before that.

Hardware constants (v5e): 197 bf16 TFLOP/s per chip, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType

# TPU v5e per-chip constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW_PER_LINK = 50e9            # bytes/s/link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_elastic_mesh(n_lost_hosts: int = 0, *, chips_per_host: int = 4,
                      multi_pod: bool = False):
    """Largest divisor-friendly degraded mesh after losing hosts.

    WRATH's environment-layer recovery (DESIGN.md §2): denylisted hosts
    shrink the ``data`` axis to the largest power of two that still fits,
    keeping ``model`` intact so parameter sharding (and thus checkpoint
    layout compatibility) is preserved.
    """
    total = (512 if multi_pod else 256) - n_lost_hosts * chips_per_host
    model = 16
    data = 1 << int(np.floor(np.log2(max(total // model, 1))))
    if multi_pod and data >= 32:
        shape, axes = (2, data // 2, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_chip_count(mesh) -> int:
    return int(np.prod(mesh.devices.shape))
