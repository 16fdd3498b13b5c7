"""Production mesh builders (functions, not module constants — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.parallel.api import ShardingRules

__all__ = ["make_production_mesh", "make_mesh", "default_rules"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2 pods =
    512 chips (pod, data, model); the pod axis composes with data for
    hierarchical DP/FSDP (or acts as the pipeline axis, see parallel.pipeline)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    return _make_mesh(tuple(shape), tuple(axes))


def default_rules(mesh, *, fsdp: bool = True, sp: bool = False) -> ShardingRules:
    """Logical-axis mapping for a mesh built by make_production_mesh (or any
    mesh with a 'data' and 'model' axis, optionally 'pod')."""
    dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
    return ShardingRules(
        dp=dp,
        tp="model",
        sp="model" if sp else None,
        ep="model",
        fsdp=dp if fsdp else None,
    )
