"""Mesh helpers for the placement rules in ``launch/sharding.py``.

Axis names follow that module's convention: "pod" carries the stacked
clients, "data" carries batch and ZeRO-style parameter/optimizer sharding,
and "model" carries tensor/expert parallelism.
"""
from __future__ import annotations


def axis_size(mesh, name: str) -> int:
    """Axis size by name; works for Mesh and AbstractMesh (both expose a
    name->size ``.shape`` mapping)."""
    return dict(mesh.shape).get(name, 1)
