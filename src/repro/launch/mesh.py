"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

Every mesh gets ``Auto`` axis types.  ``jax.make_mesh`` defaults to
``Explicit`` axes, which turn on sharding-in-types checking; the models
here place activations with sharding constraints (``dist.sharding``), and
under ``Explicit`` axes their embedding gather raises ``ShardingTypeError``
on every mesh, ``data=1,model=1`` included.

``make_production_mesh`` builds the assignment's target: one TPU v5e pod of
16×16 = 256 chips (axes ``data × model``), or two pods = 512 chips with a
leading ``pod`` axis.  Defined as functions so importing this module never
touches jax device state (device count is locked at first jax init).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh

__all__ = [
    "make_mesh",
    "make_production_mesh",
    "make_mesh_from_string",
    "parse_mesh_string",
]


def make_mesh(
    shape: Sequence[int], names: Sequence[str], *, devices=None
) -> Mesh:
    """A mesh of ``shape`` over ``names`` with ``Auto`` axis types.

    ``devices`` defaults to the process's devices; pass a described
    topology's ``devices`` for an ahead-of-time compile.
    """
    return jax.make_mesh(
        tuple(shape), tuple(names),
        axis_types=(AxisType.Auto,) * len(names), devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def parse_mesh_string(s: str) -> tuple[tuple[str, ...], tuple[int, ...]]:
    """'data=4,model=2' → (('data','model'), (4,2))."""
    names, sizes = [], []
    for part in s.split(","):
        k, v = part.split("=")
        names.append(k.strip())
        sizes.append(int(v))
    return tuple(names), tuple(sizes)


def make_mesh_from_string(s: str) -> Mesh:
    names, sizes = parse_mesh_string(s)
    return make_mesh(sizes, names)
