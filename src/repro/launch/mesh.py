"""Production mesh construction.

The target machine is a TPU v5e pod: 256 chips arranged (data=16, model=16),
multi-pod = 2 pods = 512 chips with a leading "pod" axis. ``model`` is the
paper's Processing-Lane axis (16 lanes, Table I); ``data``(×``pod``) is
batch parallelism; the cross-pod axis composes with ``data`` for the
hierarchical gradient reduction.

Functions, not module constants — importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (elastic re-mesh / tests use small shapes). Every axis
    is ``Auto``: the model code shards by ``with_sharding_constraint`` and
    leaves propagation to the compiler, which jax's default explicit axes
    reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: Optional[int] = None) -> Mesh:
    """Whatever devices exist right now, as (data, model) — used by tests,
    examples and the CPU end-to-end drivers."""
    n = len(jax.devices())
    model = model or 1
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))


def mesh_chips(mesh: Mesh) -> int:
    return mesh.devices.size


def dp_size(mesh: Mesh) -> int:
    s = 1
    for name in ("pod", "data", "replica"):
        if name in mesh.axis_names:
            s *= mesh.shape[name]
    return s


def tp_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)
