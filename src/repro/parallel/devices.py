"""Device slots — the fleet's per-worker view of the host's accelerators.

A :class:`repro.tuning.fleet.ShardedPortfolio` race wants one measurement
worker per portfolio member; on a multi-device host each worker should own a
device so members measure concurrently instead of queueing on device 0.
:func:`local_device_pool` hands out that assignment: one
:class:`DeviceSlot` per worker, round-robin over the process's local jax
devices, each slot carrying its own partition of a shared
:class:`~repro.core.costs.ExecutableCache` (the same candidate compiled for
two devices is two distinct executables — partitioned keys keep them from
colliding while the LRU budget and stats stay shared).

On a CPU-only host the slots share its CPU device(s).  A jax backend that
fails to start raises here rather than handing out device-less slots.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

__all__ = ["DeviceSlot", "local_device_pool"]


@dataclasses.dataclass
class DeviceSlot:
    """One fleet worker's execution context: its index, the jax device it
    pins measurements to, and its namespaced executable cache."""

    index: int
    device: Any
    cache: Optional[Any] = None

    def __str__(self) -> str:
        return f"slot{self.index}[{self.device}]"


def local_device_pool(num_slots: int, *, cache=None) -> List[DeviceSlot]:
    """``num_slots`` device slots over the process's local jax devices,
    round-robin (8 slots on 4 chips → each chip serves two workers).  When
    ``cache`` (an :class:`~repro.core.costs.ExecutableCache`) is given,
    every slot gets a per-*device* partition of it, so workers sharing a
    chip also share its compiled executables."""
    if num_slots < 1:
        raise ValueError(f"num_slots must be >= 1, got {num_slots}")
    import jax

    devices = list(jax.local_devices())
    slots = []
    for i in range(num_slots):
        d = i % len(devices)
        part = cache.partition(f"dev{d}") if cache is not None else None
        slots.append(DeviceSlot(index=i, device=devices[d], cache=part))
    return slots
