"""Functional optimizers (pytree in, pytree out). Adam matches torch.optim.Adam
(the paper's optimizer: lr 1e-3, weight decay 1e-4 — additive L2, not AdamW)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

Params = Any


class AdamState(NamedTuple):
    m: Params
    v: Params
    t: jnp.ndarray
    # fp32 master copy of sub-fp32 params (None when params are fp32).
    # Without it, bf16 weights near 1.0 cannot absorb lr≈1e-3 updates at all
    # (bf16 resolution at 1.0 is ~8e-3) — the canonical mixed-precision trap.
    p32: Params = None


@dataclass(frozen=True)
class Adam:
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    # Moment dtype. fp32 is the default; "bfloat16" halves optimizer HBM
    # (the dominant state term for the ≥398B archs at 256 chips) at a small
    # update-precision cost — a documented hardware-adaptation lever.
    moment_dtype: str = "float32"
    master_weights: bool = True

    def _needs_master(self, params) -> bool:
        return self.master_weights and any(
            x.dtype != jnp.float32
            for x in jax.tree_util.tree_leaves(params))

    def init(self, params: Params) -> AdamState:
        md = jnp.dtype(self.moment_dtype)
        zeros = lambda p: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, md), p)
        p32 = None
        if self._needs_master(params):
            p32 = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32), params)
        return AdamState(zeros(params), zeros(params),
                         jnp.zeros((), jnp.int32), p32)

    @jax.named_scope("fl.adam")
    def update(self, grads: Params, state: AdamState, params: Params
               ) -> Tuple[Params, AdamState]:
        t = state.t + 1
        b1, b2 = self.b1, self.b2
        md = jnp.dtype(self.moment_dtype)
        base = state.p32 if state.p32 is not None else params
        if self.weight_decay:
            grads = jax.tree_util.tree_map(
                lambda g, p: g + self.weight_decay * p.astype(g.dtype),
                grads, base)
        gf = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        m = jax.tree_util.tree_map(
            lambda m, g: (b1 * m.astype(jnp.float32) + (1 - b1) * g).astype(md),
            state.m, gf)
        v = jax.tree_util.tree_map(
            lambda v, g: (b2 * v.astype(jnp.float32) + (1 - b2) * g * g).astype(md),
            state.v, gf)
        tf = t.astype(jnp.float32)
        c1 = 1 - b1 ** tf
        c2 = 1 - b2 ** tf

        def upd32(p, m, v):
            step = self.lr * (m.astype(jnp.float32) / c1) / (
                jnp.sqrt(v.astype(jnp.float32) / c2) + self.eps)
            return p.astype(jnp.float32) - step

        new32 = jax.tree_util.tree_map(upd32, base, m, v)
        new_params = jax.tree_util.tree_map(
            lambda n, p: n.astype(p.dtype), new32, params)
        p32 = new32 if state.p32 is not None else None
        return new_params, AdamState(m, v, t, p32)


@dataclass(frozen=True)
class SGD:
    lr: float = 0.1
    weight_decay: float = 0.0

    def init(self, params: Params):
        return ()

    def update(self, grads, state, params):
        if self.weight_decay:
            grads = jax.tree_util.tree_map(
                lambda g, p: g + self.weight_decay * p.astype(g.dtype), grads, params)
        new = jax.tree_util.tree_map(
            lambda p, g: (p.astype(jnp.float32) - self.lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return new, state
