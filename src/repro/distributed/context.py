"""Ambient mesh for model-internal shard_map regions.

Model code (attention, MoE) sometimes needs the mesh to build a
shard_map region (seq-sharded flash decode, expert-parallel dispatch).
Launchers set it; single-device tests leave it unset and the model falls
back to mesh-free implementations.
"""
from __future__ import annotations

from typing import Optional, Tuple

from jax.sharding import Mesh

_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _MESH
    _MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _MESH


def dp_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
