"""Checkpointing: params/optimizer pytrees <-> .npz + path manifest.

Leaves are stored under '/'-joined key paths so checkpoints are inspectable
with plain numpy and stable across JAX versions. Restoration matches leaves
BY KEY PATH (never by flatten order): a checkpoint whose key set disagrees
with the template raises a descriptive error listing the missing and
unexpected keys instead of silently loading values into the wrong slots.
Round-level federation state (client models, de-bias weights, accountant
counters) serializes the same way — see :mod:`repro.checkpoint.federation`.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict

import jax
import numpy as np


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return f"[{p.idx}]"
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def _flatten_with_paths(tree) -> Dict[str, Any]:
    """Leaf dict keyed by '/'-joined path; rejects ambiguous (colliding)
    key paths up front — a collision would otherwise drop a leaf and
    corrupt whichever restore consumed the checkpoint."""
    flat: Dict[str, Any] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(_path_str(p) for p in path)
        if key in flat:
            raise ValueError(
                f"pytree produces duplicate checkpoint key path {key!r}; "
                "rename the colliding nodes before checkpointing")
        flat[key] = leaf
    return flat


# public alias: the commitment layer (repro.core.commit) flattens proxy
# trees with THE SAME path convention the npz uses, so a commitment
# computed from live state and one recomputed from the checkpoint agree
# by construction
flatten_with_paths = _flatten_with_paths


def _npz_path(path: str) -> str:
    return path if path.endswith(".npz") else path + ".npz"


def manifest_path(path: str) -> str:
    return (path[:-4] if path.endswith(".npz") else path) + ".json"


def save_checkpoint(path: str, tree) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    flat = _flatten_with_paths(tree)
    arrays = {}
    for k, v in flat.items():
        a = np.asarray(v)
        if a.dtype.kind not in "fiub" or str(a.dtype) == "bfloat16":
            # npz has no bf16/fp8 codecs; store widened (lossless into f32)
            a = a.astype(np.float32)
        arrays[k] = a
    np.savez(_npz_path(path), **arrays)
    manifest = {k: {"shape": list(np.shape(v)), "dtype": str(np.asarray(v).dtype)}
                for k, v in flat.items()}
    with open(manifest_path(path), "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)


def load_checkpoint(path: str, like) -> Any:
    """Restore into the structure of ``like``, matching leaves by key path.

    Raises ``KeyError`` when the checkpoint's key set and the template's
    disagree (listing the missing / unexpected paths) and ``ValueError``
    on a per-leaf shape mismatch — both conditions previously restored
    garbage silently when flatten order happened to differ. Leaves come
    back as host (numpy) arrays in the template's dtypes; the caller
    decides where they go.
    """
    pairs, treedef = jax.tree_util.tree_flatten_with_path(like)
    keyed = {}
    for p, leaf in pairs:
        key = "/".join(_path_str(s) for s in p)
        if key in keyed:
            raise ValueError(
                f"restore template produces duplicate key path {key!r}")
        keyed[key] = leaf
    with np.load(_npz_path(path)) as npz:
        have = set(npz.files)
        missing = sorted(set(keyed) - have)
        unexpected = sorted(have - set(keyed))
        if missing or unexpected:
            raise KeyError(
                f"checkpoint {_npz_path(path)!r} does not match the restore "
                f"template: missing keys {missing or 'none'}, "
                f"unexpected keys {unexpected or 'none'}")
        restored = []
        for p, leaf in pairs:
            key = "/".join(_path_str(s) for s in p)
            arr = npz[key]
            if arr.shape != tuple(np.shape(leaf)):
                raise ValueError(
                    f"checkpoint leaf {key!r} has shape {arr.shape}, "
                    f"template expects {tuple(np.shape(leaf))}")
            dt = leaf.dtype if hasattr(leaf, "dtype") else None
            restored.append(arr.astype(dt) if dt is not None else arr)
    return jax.tree_util.tree_unflatten(treedef, restored)
