"""Weights made from the seed, for the system under test and for the plain
reference alike.

A model's weights are a tree of named arrays in the layout the reference
reads (``bench/reference_lm.py``); the harness
checks that the program's own parameter tree has exactly that layout before
it hands the weights over. Every array is drawn from its own key, folded from
the run's weight key, the client, the role and the leaf's place in the tree,
so one client's tree can be made again without the others.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

ROLES = {"private": 0, "proxy": 1}

#: ``rule(path, shape) -> ("normal", std) | ("ones",) | ("zeros",)``
Rule = Callable[[Tuple[str, ...], Tuple[int, ...]], Tuple]


def path_names(path) -> Tuple[str, ...]:
    """A tree path as plain strings (dict keys and sequence indices)."""
    out = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                out.append(str(getattr(p, attr)))
                break
    return tuple(out)


def make_params(key, client: int, role: str, layout, rule: Rule):
    """The ``role`` weights of ``client``: ``layout`` is a tree of
    ``jax.ShapeDtypeStruct``; each leaf is drawn by ``rule``."""
    base = jax.random.fold_in(jax.random.fold_in(key, client), ROLES[role])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(layout)
    out = []
    for i, (path, sd) in enumerate(leaves):
        kind = rule(path_names(path), tuple(sd.shape))
        if kind[0] == "ones":
            out.append(jnp.ones(sd.shape, sd.dtype))
        elif kind[0] == "zeros":
            out.append(jnp.zeros(sd.shape, sd.dtype))
        else:
            x = jax.random.normal(jax.random.fold_in(base, i), sd.shape,
                                  jnp.float32) * kind[1]
            out.append(x.astype(sd.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def same_layout(a, b) -> bool:
    """True when two trees have one structure and equal leaf shapes and
    dtypes."""
    fa, ta = jax.tree_util.tree_flatten(a)
    fb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(
        tuple(x.shape) == tuple(y.shape) and jnp.dtype(x.dtype) == jnp.dtype(
            y.dtype) for x, y in zip(fa, fb))


def describe(tree) -> Dict[str, Tuple]:
    """``{path: shape}`` of a tree, for error messages."""
    return {"/".join(path_names(p)): tuple(x.shape)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
