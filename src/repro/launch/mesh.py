"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
data-parallel across the DCN (gradients reduce over pod+data; the 2.5-D
GEMM schedule can also use it as the C-replication axis).

A 2-stage inter-pod *pipeline* topology would reuse the same function with
axes ("stage", "data", "model") and microbatch round-robin over "stage";
on this fixed 512-chip assignment plain pod-DP wins (see DESIGN.md §6),
so PP is not instantiated.
"""

from __future__ import annotations

from typing import Tuple

import jax

def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` over the visible devices, every axis Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def abstract_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """A device-free mesh of this shape, for sharding rules and specs."""
    return jax.sharding.AbstractMesh(shape, axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape: Tuple[int, ...] = None, axes=None):
    """Small mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1, n) if n > 1 else (1, 1)
        axes = ("data", "model")
    return make_mesh(shape, axes)


def batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def n_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
