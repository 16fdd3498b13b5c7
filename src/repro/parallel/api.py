"""Logical-axis sharding context: model code asks for logical placements,
the active context maps them to mesh axes (with divisibility guards).

Model code calls ``constrain(x, ("dp", "sp", None))`` at block boundaries;
without an active context this is a no-op (single-device tests), inside
``sharding_context(mesh, rules)`` it becomes a with_sharding_constraint.

Logical axes:
  dp  — data parallel (batch dims):        ("data",) or ("pod", "data")
  tp  — tensor parallel (heads/ff/vocab):  "model"
  sp  — sequence parallel (activations):   None (off) or "model"
  ep  — expert parallel:                   "model"
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

__all__ = ["ShardingRules", "sharding_context", "constrain", "current_rules", "logical_spec"]

Axis = Union[None, str, Tuple[str, ...]]

_TLS = threading.local()


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    dp: Axis = ("data",)
    tp: Axis = "model"
    sp: Axis = None  # sequence-parallel activations
    ep: Axis = "model"
    fsdp: Axis = ("data",)  # weight sharding axes (ZeRO-3); None disables

    def resolve(self, logical: Optional[str]) -> Axis:
        if logical is None:
            return None
        return getattr(self, logical)


def _axis_size(mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis]
    return int(np.prod([mesh.shape[a] for a in axis]))


def logical_spec(mesh, rules: ShardingRules, shape, wanted) -> PartitionSpec:
    """Map logical axes to a PartitionSpec, dropping any axis whose size does
    not divide the corresponding dim (JAX requires exact divisibility)."""
    entries = []
    for dim, logical in zip(shape, wanted):
        axis = rules.resolve(logical)
        if axis is not None and dim % _axis_size(mesh, axis) == 0 and dim > 0:
            entries.append(axis)
        else:
            entries.append(None)
    return PartitionSpec(*entries)


@contextlib.contextmanager
def sharding_context(mesh, rules: ShardingRules):
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = (mesh, rules)
    try:
        yield
    finally:
        _TLS.ctx = prev


def current_rules():
    return getattr(_TLS, "ctx", None)


def constrain(x, wanted):
    """Apply a logical-axes sharding constraint if a context is active."""
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_spec(mesh, rules, x.shape, wanted)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_first(x, *options):
    """Apply the first option whose every requested axis survives the
    divisibility guard (fallback: the first option, with drops)."""
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    for wanted in options:
        spec = logical_spec(mesh, rules, x.shape, wanted)
        requested = sum(1 for w in wanted if w is not None and rules.resolve(w) is not None)
        granted = sum(1 for e in spec if e is not None)
        if granted == requested:
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    spec = logical_spec(mesh, rules, x.shape, options[0])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
