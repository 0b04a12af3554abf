"""Device mesh construction + chip-group assignment.

New design territory (SURVEY.md §2 parallelism inventory: the reference has
none): models larger than one chip are served by a *chip group* — a sub-mesh
of the pod slice — and the consistent-hash ring assigns models to groups
instead of single chips. Within a group, XLA collectives ride ICI; the
request/routing plane between hosts stays gRPC over DCN (SURVEY.md §5
distributed-backend note).
"""

from __future__ import annotations

import numpy as np
from jax.sharding import Mesh


def make_mesh(axes: dict[str, int], devices=None) -> Mesh:
    """Mesh from {axis: size}; total must divide available devices.

    Axis order follows dict order; put the fastest-varying (tensor/model)
    axis last so it maps to adjacent devices — adjacent = shortest ICI hops
    on a TPU slice.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    total = int(np.prod(list(axes.values())))
    if total > len(devices):
        raise ValueError(f"mesh {axes} needs {total} devices, have {len(devices)}")
    arr = np.array(devices[:total]).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes))


def chip_groups(devices, group_size: int) -> list[list]:
    """Partition devices into contiguous groups of ``group_size`` (contiguous
    = ICI-adjacent on a slice). The ring's members become group ids."""
    if group_size < 1:
        raise ValueError("group_size must be >= 1")
    if len(devices) % group_size:
        raise ValueError(f"{len(devices)} devices not divisible into groups of {group_size}")
    return [list(devices[i : i + group_size]) for i in range(0, len(devices), group_size)]


def group_mesh(devices, group_size: int, group_index: int, axis: str = "model") -> Mesh:
    groups = chip_groups(devices, group_size)
    return Mesh(np.array(groups[group_index]), (axis,))
