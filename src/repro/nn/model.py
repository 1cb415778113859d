"""Decoder-only model stack for every assigned architecture family.

The layer layout is ``prefix`` (unrolled) + ``pattern`` × R (stacked params,
executed with ``lax.scan`` so HLO size is O(len(pattern)), not O(n_layers) —
keeping compile time flat in depth) + ``tail``
(unrolled remainder). KV/SSM caches mirror the same structure so the decode
path scans too.

Modality frontends are stubs per the task carve-out: VLM forward consumes
precomputed patch embeddings [B, n_img, frontend_dim]; audio forward
consumes EnCodec token ids [B, S, n_codebooks].
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .blocks import apply_layer, init_layer, init_layer_cache
from .modules import Params, init_linear, init_rmsnorm, linear, normal_init, rmsnorm

Cache = Dict[str, Any]


def _plan(cfg: ModelConfig):
    P = len(cfg.prefix)
    L = len(cfg.pattern)
    R, rem = cfg.pattern_plan()
    return P, L, R, rem


# ---------------------------------------------------------------------------
# init


def init_model(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    P, L, R, rem = _plan(cfg)
    keys = jax.random.split(key, 8)
    params: Params = {}

    if cfg.modality == "audio":
        params["embed"] = {"e": normal_init(keys[0], (cfg.n_codebooks, cfg.vocab_size, cfg.d_model), 0.02, dtype)}
    else:
        params["embed"] = {"e": normal_init(keys[0], (cfg.vocab_size, cfg.d_model), 0.02, dtype)}
    if cfg.modality == "vlm":
        params["img_proj"] = init_linear(keys[1], cfg.frontend_dim, cfg.d_model, bias=True, dtype=dtype)

    lk = jax.random.split(keys[2], max(P, 1))
    params["prefix"] = tuple(init_layer(lk[i], cfg, cfg.prefix[i], dtype) for i in range(P))

    if R > 0:
        stack = []
        pk = jax.random.split(keys[3], L)
        for pos in range(L):
            rk = jax.random.split(pk[pos], R)
            stack.append(jax.vmap(lambda k: init_layer(k, cfg, cfg.pattern[pos], dtype))(rk))
        params["stack"] = tuple(stack)
    else:
        params["stack"] = ()

    tk = jax.random.split(keys[4], max(rem, 1))
    params["tail"] = tuple(init_layer(tk[i], cfg, cfg.pattern[i], dtype) for i in range(rem))

    params["norm_f"] = init_rmsnorm(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        if cfg.modality == "audio":
            params["head"] = {"w": normal_init(keys[5], (cfg.n_codebooks, cfg.d_model, cfg.vocab_size), 0.02, dtype)}
        else:
            params["head"] = init_linear(keys[5], cfg.d_model, cfg.vocab_size, dtype=dtype)
    return params


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> Cache:
    dtype = dtype or jnp.dtype(cfg.dtype)
    P, L, R, rem = _plan(cfg)
    cache: Cache = {
        "prefix": tuple(init_layer_cache(cfg, cfg.prefix[i], batch, max_len, dtype) for i in range(P)),
        "tail": tuple(init_layer_cache(cfg, cfg.pattern[i], batch, max_len, dtype) for i in range(rem)),
    }
    if R > 0:
        stack = []
        for pos in range(L):
            one = init_layer_cache(cfg, cfg.pattern[pos], batch, max_len, dtype)
            stack.append(jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (R,) + x.shape), one))
        cache["stack"] = tuple(stack)
    else:
        cache["stack"] = ()
    return cache


# ---------------------------------------------------------------------------
# forward


def _embed_inputs(params, cfg: ModelConfig, tokens, img):
    if cfg.modality == "audio":
        # tokens: [B, S, K]; sum codebook embeddings
        e = params["embed"]["e"]  # [K, V, d]
        x = sum(jnp.take(e[k], tokens[..., k], axis=0) for k in range(cfg.n_codebooks))
        return x
    e = params["embed"]["e"]
    x = jnp.take(e, tokens, axis=0)
    if cfg.modality == "vlm" and img is not None:
        xi = linear(params["img_proj"], img.astype(x.dtype))
        x = jnp.concatenate([xi, x], axis=1)
    return x


def _logits(params, cfg: ModelConfig, x):
    if cfg.modality == "audio":
        if cfg.tie_embeddings:
            return jnp.einsum("bsd,kvd->bskv", x, params["embed"]["e"])
        return jnp.einsum("bsd,kdv->bskv", x, params["head"]["w"])
    if cfg.tie_embeddings:
        return x @ params["embed"]["e"].T
    return linear(params["head"], x)


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    img: Optional[jnp.ndarray] = None,
    *,
    cache: Optional[Cache] = None,
    pos_offset=0,
    remat: bool = False,
    kv_chunk: int = 1024,
    mamba_chunk: int = 256,
) -> Tuple[jnp.ndarray, Optional[Cache], jnp.ndarray]:
    """Returns (logits, new_cache, aux_loss). ``cache=None`` → pure forward
    (training); with cache → prefill (S>1) or decode (S==1) at
    ``pos_offset``."""
    P, L, R, rem = _plan(cfg)
    x = _embed_inputs(params, cfg, tokens, img)
    aux = jnp.zeros((), jnp.float32)
    use_cache = cache is not None
    new_cache: Cache = {"prefix": [], "tail": [], "stack": ()}
    layer_kw = dict(pos_offset=pos_offset, kv_chunk=kv_chunk,
                    mamba_chunk=mamba_chunk)

    for i in range(P):
        x, nc, a = apply_layer(params["prefix"][i], cfg, cfg.prefix[i], x,
                               cache=cache["prefix"][i] if use_cache else None, **layer_kw)
        aux += a
        new_cache["prefix"].append(nc)

    if R > 0:
        if use_cache:
            def body(carry, xs):
                x, aux = carry
                pp, cc = xs
                ncs = []
                for pos in range(L):
                    x, nc, a = apply_layer(pp[pos], cfg, cfg.pattern[pos], x,
                                           cache=cc[pos], **layer_kw)
                    aux += a
                    ncs.append(nc)
                return (x, aux), tuple(ncs)

            xs = (params["stack"], cache["stack"])
        else:
            def body(carry, pp):
                x, aux = carry
                for pos in range(L):
                    x, _, a = apply_layer(pp[pos], cfg, cfg.pattern[pos], x,
                                          cache=None, **layer_kw)
                    aux += a
                return (x, aux), None

            xs = params["stack"]
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux), stack_cache = jax.lax.scan(body, (x, aux), xs)
        new_cache["stack"] = stack_cache if use_cache else ()

    for i in range(rem):
        x, nc, a = apply_layer(params["tail"][i], cfg, cfg.pattern[i], x,
                               cache=cache["tail"][i] if use_cache else None, **layer_kw)
        aux += a
        new_cache["tail"].append(nc)

    x = rmsnorm(params["norm_f"], x)
    logits = _logits(params, cfg, x)
    if use_cache:
        out_cache = {"prefix": tuple(new_cache["prefix"]),
                     "stack": new_cache["stack"],
                     "tail": tuple(new_cache["tail"])}
    else:
        out_cache = None
    return logits, out_cache, aux


def decode_step(params, cfg: ModelConfig, tokens_last, cache, pos):
    """One-token decode. tokens_last: [B,1] (or [B,1,K] audio)."""
    logits, new_cache, _ = forward(params, cfg, tokens_last, cache=cache, pos_offset=pos)
    return logits, new_cache
