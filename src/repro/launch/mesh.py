"""Production mesh construction (assignment-mandated shapes).

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches JAX device state.  The single-pod mesh
is 16 x 16 = 256 chips ("data", "model"); the multi-pod mesh stacks a "pod"
axis in front: 2 x 16 x 16 = 512 chips.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model code places data with sharding constraints and
    # leaves propagation to the partitioner (jax.make_mesh defaults to
    # Explicit axes, which type every intermediate's sharding)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over the actually-available devices (tests/examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, n // data)
    return _mesh((data, model), ("data", "model"))
