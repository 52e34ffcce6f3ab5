"""Mesh factory — the one place the repo builds a ``jax.sharding.Mesh``.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state. Every mesh is built with ``AxisType.Auto`` axes. JAX 0.9's
``jax.make_mesh`` defaults to Explicit axes, under which every reshape or
slice of a sharded value must name its output sharding; the shard_map
paths (core/shard.py, core/search.py, core/search_sharded.py,
streaming/updates.py) place their operands themselves and want the
compiler-propagated (Auto) behaviour.

The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
(in launch/dryrun.py, before any jax import) so the production shapes are
buildable on the CPU host; on real hardware the same call maps onto the
v5e pod slices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """Auto-axis mesh of ``shape`` over ``devices`` (default: the first
    ``prod(shape)`` devices JAX reports)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def check_auto(mesh) -> None:
    """Reject a mesh with non-Auto axes (``jax.make_mesh``'s JAX 0.9
    default): the sharded paths reshape and slice their sharded outputs,
    which an Explicit-axis mesh refuses without per-site shardings."""
    bad = [a for a, t in zip(mesh.axis_names, mesh.axis_types)
           if t != AxisType.Auto]
    if bad:
        raise ValueError(
            f"mesh axes {bad} are not AxisType.Auto: build the mesh with "
            "repro.launch.mesh.make_mesh")


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
