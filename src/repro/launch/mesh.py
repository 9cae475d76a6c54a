"""Production meshes.

``make_production_mesh`` is a FUNCTION (never module-level state) so that
importing this module never touches jax device initialization — the dry-run
sets XLA_FLAGS before any jax import and only then builds meshes.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the "pod" axis crosses
    the DCI between pods, and only gradient/batch traffic rides it."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:n])


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the host's devices (the train CLI, tests)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
