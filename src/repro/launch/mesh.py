"""Production mesh definitions.

Single pod: 16×16 = 256 chips (TPU v5e pod), axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model).

Every mesh of the program is built by :func:`make_mesh`.  The mesh
builders are functions (not module-level constants) so that importing this
module never touches jax device state.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """A device mesh with **Auto** axes.

    The train and serve steps run their own collectives inside
    ``shard_map`` and hand shardings to ``jit`` as ``NamedSharding``s; the
    arrays they return must stay usable by plain jnp code outside the map
    (decode, checkpointing).  ``jax.make_mesh`` defaults to Explicit axes,
    which put the sharding into every array's type and make such code
    raise ``ShardingTypeError``.  ``devices`` defaults to all devices;
    pass a slice to run on fewer (or a described topology's devices to
    compile without a chip)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small mesh for CI on a host with 8 fake devices."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    assert "model" in mesh.axis_names
    return "model"


def mesh_info(mesh):
    dp = 1
    for a in data_axes(mesh):
        dp *= mesh.shape[a]
    return {
        "data_parallel": dp,
        "model_parallel": mesh.shape["model"],
        "chips": dp * mesh.shape["model"],
        "axis_names": tuple(mesh.axis_names),
    }
