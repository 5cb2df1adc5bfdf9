"""Activation sharding constraints (GSPMD hints) for model internals.

Model code is mesh-agnostic; steps/dryrun set the ambient mesh here before
tracing, and blocks call ``constrain(x, "dp", "tp", None)`` with logical
roles per dimension:

  "dp"  -> the data-parallel axes present in the mesh (("pod","data"))
  "tp"  -> the tensor-parallel axis ("model")
  None  -> replicated / unconstrained

Without an ambient mesh (smoke tests, serving on 1 device) it's a no-op.
GSPMD occasionally picks pathological partitionings for MoE dispatch
einsums (observed: ~8x effective parallelism on a 256-chip mesh); these
constraints pin the intended sharding and are part of the *baseline*
config, matching how production MoE frameworks annotate dispatch.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _MESH
    _MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _MESH


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def constrain(x: jax.Array, *roles) -> jax.Array:
    mesh = _MESH
    if mesh is None:
        return x
    assert len(roles) == x.ndim, (roles, x.shape)
    spec = []
    for dim, role in zip(x.shape, roles):
        if role == "dp":
            axes = [a for a in _dp_axes(mesh)]
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if axes and dim % size == 0:
                spec.append(tuple(axes) if len(axes) > 1 else axes[0])
            else:
                spec.append(None)
        elif role == "tp":
            if "model" in mesh.axis_names and dim % mesh.shape["model"] == 0:
                spec.append("model")
            else:
                spec.append(None)
        else:
            spec.append(None)
    # NamedSharding (not bare PartitionSpec) so tracing works outside a
    # `with mesh:` context (e.g. Trainer steps traced at first call).
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
