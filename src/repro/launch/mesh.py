"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before anything else imports jax.
"""
from __future__ import annotations

from jax import make_mesh
from jax.sharding import AxisType

AUTO = AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod-slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
    an outer data-parallel axis (the paper's inter-node DP)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AUTO,) * len(axes))


def make_production_mesh_2d(*, multi_pod: bool = False):
    """Mesh variant for 2-D Jigsaw (paper's 4-way generalized to 4x4):
    the 16-way model axis factored into (mdom=4, mtp=4)."""
    shape = (2, 16, 4, 4) if multi_pod else (16, 4, 4)
    axes = (("pod", "data", "mdom", "mtp") if multi_pod
            else ("data", "mdom", "mtp"))
    return make_mesh(shape, axes, axis_types=(AUTO,) * len(axes))


def make_host_mesh(model: int = 4, data: int = 2, *, two_d: bool = False):
    """Small mesh over host-emulated devices (tests, examples)."""
    if two_d:
        import math
        q = int(math.isqrt(model))
        assert q * q == model
        return make_mesh((data, q, q), ("data", "mdom", "mtp"),
                         axis_types=(AUTO,) * 3)
    return make_mesh((data, model), ("data", "model"),
                     axis_types=(AUTO,) * 2)
