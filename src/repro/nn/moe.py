"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Dispatch is gather/scatter based (argsort tokens by expert, place into
[E, C] capacity slots) rather than one-hot-einsum based, so the compiled
FLOPs stay ≈ the active-expert FFN FLOPs.

Supports DeepSeek-style shared (always-on) experts and Arctic-style
parallel dense-residual MLPs.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, MoEConfig
from .modules import Params, init_mlp, mlp, normal_init


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    mo = cfg.moe or MoEConfig()
    d, E, f = cfg.d_model, mo.n_experts, mo.d_ff_expert
    k = jax.random.split(key, 6)
    p = {
        "router": normal_init(k[0], (d, E), 0.02, jnp.float32),
        "gate": normal_init(k[1], (E, d, f), 0.02, dtype),
        "up": normal_init(k[2], (E, d, f), 0.02, dtype),
        "down": normal_init(k[3], (E, f, d), 0.02, dtype),
    }
    if mo.n_shared_experts:
        p["shared"] = init_mlp(k[4], d, mo.n_shared_experts * f, dtype)
    if mo.dense_residual_d_ff:
        p["residual"] = init_mlp(k[5], d, mo.dense_residual_d_ff, dtype)
    return p


def capacity(n_tokens: int, mo: MoEConfig) -> int:
    return max(1, int(-(-n_tokens * mo.top_k * mo.capacity_factor // mo.n_experts)))


def apply_moe(p: Params, cfg: ModelConfig,
              x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] -> (y [B, S, d], aux_loss scalar)."""
    mo = cfg.moe or MoEConfig()
    B, S, d = x.shape
    T, E, K = B * S, mo.n_experts, mo.top_k
    C = capacity(T, mo)
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32)) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
    topw, topi = jax.lax.top_k(probs, K)  # [T, K]
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch-style)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(1), axis=0
    ) / K
    frac_probs = jnp.mean(probs, axis=0)
    aux = mo.router_aux_loss_coef * E * jnp.sum(frac_tokens * frac_probs)

    # --- sort-based dispatch into [E, C] capacity slots
    flat_e = topi.reshape(-1)  # [T*K]
    flat_t = jnp.arange(T * K, dtype=jnp.int32) // K
    flat_w = topw.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos < C

    # 2D scatter into [E, C, d] capacity slots
    slot_c = jnp.where(keep, pos, 0)
    src = xf[st] * keep[:, None].astype(x.dtype)
    be = jnp.zeros((E, C, d), x.dtype).at[se, slot_c].add(src)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", be, p["gate"])) * jnp.einsum(
        "ecd,edf->ecf", be, p["up"]
    )
    ye = jnp.einsum("ecf,efd->ecd", h, p["down"])

    w = (sw * keep).astype(x.dtype)
    yf = jnp.zeros((T, d), x.dtype).at[st].add(ye[se, slot_c] * w[:, None])

    y = yf.reshape(B, S, d)
    if "shared" in p:
        y = y + mlp(p["shared"], x)
    if "residual" in p:
        y = y + mlp(p["residual"], x)
    return y, aux
