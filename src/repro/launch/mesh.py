"""Mesh construction — the one place the repo builds a ``jax.sharding.Mesh``.

The target is a TPU v5e pod: 256 chips as a (data=16, model=16) mesh, or
two pods as (pod=2, data=16, model=16). ``model`` carries tensor
parallelism for attention/dense-FFN/vocab and expert parallelism for MoE;
``data``/``pod`` shard the batch (and, with fsdp, parameter storage).

Every axis is ``AxisType.Auto``: the model code pins layouts with
``with_sharding_constraint`` and ``shard_map`` specs, which only GSPMD
(Auto) axes accept — ``jax.make_mesh`` defaults to Explicit axes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first
jax init, and smoke tests must keep seeing 1 CPU device.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """Auto-axis mesh of ``shape`` over ``axes`` (``devices``: explicit
    device list, e.g. a described topology's devices for compile-only
    rehearsals; default: the backend's devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dev_mesh(data: int = 2, model: int = 4, *, devices=None):
    """(data, model) mesh: fake CPU devices in tests, chips on a host."""
    return make_mesh((data, model), ("data", "model"), devices=devices)


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]


def batch_shards(mesh) -> int:
    n = 1
    for name in ("pod", "data"):
        if name in mesh.axis_names:
            n *= mesh.shape[name]
    return n
