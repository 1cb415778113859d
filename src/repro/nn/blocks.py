"""Decoder layer = pre-norm token mixer (attn/MLA/mamba) + pre-norm FFN
(dense / MoE / none), with residuals. Uniform (x, cache, aux) interface so
the model stack can `lax.scan` over stacked per-layer parameters."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import LayerSpec, ModelConfig
from .attention import apply_attention, init_attention, init_kv_cache
from .mamba import apply_mamba, init_mamba, init_mamba_cache
from .mla import apply_mla, init_mla, init_mla_cache
from .modules import Params, init_mlp, init_rmsnorm, mlp, rmsnorm
from .moe import apply_moe, init_moe


def init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype=jnp.float32) -> Params:
    k1, k2 = jax.random.split(key)
    p: Params = {"norm1": init_rmsnorm(cfg.d_model, dtype)}
    if spec.kind == "attn":
        if cfg.attn_impl == "mla":
            p["mixer"] = init_mla(k1, cfg, dtype)
        else:
            p["mixer"] = init_attention(k1, cfg, dtype)
    elif spec.kind == "mamba":
        p["mixer"] = init_mamba(k1, cfg, dtype)
    else:
        raise ValueError(spec.kind)
    if spec.ffn == "dense":
        p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
        p["ffn"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    elif spec.ffn == "moe":
        p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
        p["ffn"] = init_moe(k2, cfg, dtype)
    elif spec.ffn != "none":
        raise ValueError(spec.ffn)
    return p


def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int,
                     dtype=jnp.float32) -> Params:
    if spec.kind == "mamba":
        return init_mamba_cache(cfg, batch, dtype)
    if cfg.attn_impl == "mla":
        return init_mla_cache(cfg, batch, max_len, dtype)
    return init_kv_cache(cfg, batch, max_len, dtype, window=spec.window)


def apply_layer(
    p: Params,
    cfg: ModelConfig,
    spec: LayerSpec,
    x: jnp.ndarray,
    *,
    pos_offset=0,
    cache: Optional[Params] = None,
    kv_chunk: int = 1024,
    mamba_chunk: int = 256,
) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
    h = rmsnorm(p["norm1"], x)
    if spec.kind == "mamba":
        y, new_cache = apply_mamba(p["mixer"], cfg, h, cache=cache,
                                   chunk=mamba_chunk)
    elif cfg.attn_impl == "mla":
        y, new_cache = apply_mla(p["mixer"], cfg, spec, h, pos_offset=pos_offset,
                                 cache=cache, kv_chunk=kv_chunk)
    else:
        y, new_cache = apply_attention(p["mixer"], cfg, spec, h, pos_offset=pos_offset,
                                       cache=cache, kv_chunk=kv_chunk)
    x = x + y
    aux = jnp.zeros((), jnp.float32)
    if "ffn" in p:
        h = rmsnorm(p["norm2"], x)
        if spec.ffn == "moe":
            y, aux = apply_moe(p["ffn"], cfg, h)
        else:
            y = mlp(p["ffn"], h)
        x = x + y
    return x, new_cache, aux
