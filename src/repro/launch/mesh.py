"""Production meshes.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model").

In the ProxyFL mapping each *pod is one federated client* (an institution's
own slice of the fleet): client state is stacked on a leading axis sharded
over "pod", and the PushSum proxy exchange runs along "pod". "data" carries
batch + ZeRO-style parameter/optimizer sharding (FSDP), "model" carries
tensor/expert parallelism.

Functions, not module-level constants — importing this module must never
touch jax device state (the dry-run forces a 512-device host platform
before any jax initialization; tests/benches must keep seeing 1 device).
"""
from __future__ import annotations

from typing import Tuple

import jax

TPU_V5E = {
    "peak_flops_bf16": 197e12,  # per chip
    "hbm_bandwidth": 819e9,  # bytes/s
    "ici_bandwidth": 50e9,  # bytes/s per link
    "hbm_bytes": 16 * 2 ** 30,
}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_client_mesh(n_clients: int = 16, model: int = 16):
    """Distributed-gossip demo mesh: one federated client per 'client' index."""
    return jax.make_mesh((n_clients, model), ("client", "model"))


def fsdp_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes used for batch/FSDP sharding (everything except model/pod)."""
    return tuple(a for a in mesh.axis_names if a in ("data",))


def batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("data",))


def n_pods(mesh) -> int:
    return dict(mesh.shape).get("pod", 1)


def axis_size(mesh, name: str) -> int:
    """Axis size by name; works for Mesh and AbstractMesh (both expose a
    name->size ``.shape`` mapping)."""
    return dict(mesh.shape).get(name, 1)
