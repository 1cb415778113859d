"""Attention: GQA/MHA with causal, sliding-window and KV-cache decode paths.

The core primitive is :func:`attend` — an online-softmax attention that
scans over KV chunks so the S×S score matrix is never materialized (the
pure-JAX analogue of the Pallas flash kernel in ``repro.kernels``; XLA maps
the per-chunk einsums onto the MXU). It supports

* grouped queries (``Hq = G * Hkv``) without repeating KV heads,
* different QK and V head dims (needed by MLA's absorbed decode),
* causal + sliding-window masking via explicit position vectors,
* arbitrary query offset (decode with a prefix cache).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, LayerSpec
from .modules import Params, apply_rope, init_linear, linear

NEG_INF = float("-inf")


def _mask(q_pos, kv_pos, window: Optional[int]):
    """[Sq, Sk] boolean validity mask (True == attend)."""
    ok = kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= (q_pos[:, None] - kv_pos[None, :]) < window
    return ok


def _attend_dense(q, k, v, q_pos, kv_pos, window, scale):
    """Single-block attention (small Skv). q:[B,Sq,Hkv,G,Dqk]."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    ok = _mask(q_pos, kv_pos, window)
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)  # fully-masked rows
    p = jnp.where(ok[None, None, None], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]


@jax.named_scope("fl.attention")
def attend(
    q: jnp.ndarray,  # [B, Sq, Hq, Dqk]
    k: jnp.ndarray,  # [B, Sk, Hkv, Dqk]
    v: jnp.ndarray,  # [B, Sk, Hkv, Dv]
    *,
    q_pos: jnp.ndarray,  # [Sq] int32 absolute positions
    kv_pos: jnp.ndarray,  # [Sk] int32 absolute positions
    window: Optional[int] = None,
    kv_chunk: int = 1024,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Online-softmax causal attention; returns [B, Sq, Hq, Dv] (q dtype)."""
    B, Sq, Hq, Dqk = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = scale if scale is not None else Dqk ** -0.5
    qr = q.reshape(B, Sq, Hkv, G, Dqk)

    if Sk <= kv_chunk:
        out = _attend_dense(qr, k, v, q_pos, kv_pos, window, scale)
        return out.reshape(B, Sq, Hq, Dv).astype(q.dtype)

    # pad Sk to a multiple of the chunk; padded slots get kv_pos = INT32_MAX
    n_chunks = -(-Sk // kv_chunk)
    pad = n_chunks * kv_chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=jnp.iinfo(jnp.int32).max)
    kc = k.reshape(B, n_chunks, kv_chunk, Hkv, Dqk).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, kv_chunk, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(n_chunks, kv_chunk)

    qf = qr.astype(jnp.float32)

    # Flash-attention semantics under AD: without checkpointing, lax.scan
    # saves every chunk's probability block as a backward residual — the
    # full S×S score matrix in fp32. Rematerializing the body keeps only
    # the O(S) carry per chunk and recomputes p in the backward pass.
    @jax.checkpoint
    def body(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kb.astype(jnp.float32)) * scale
        ok = _mask(q_pos, pb, window)
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(ok[None, None, None], jnp.exp(s - m_safe[..., None]), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dv)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer


def init_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_linear(kq, cfg.d_model, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_linear(kk, cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_linear(kv, cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_linear(ko, cfg.n_heads * hd, cfg.d_model, dtype=dtype),
    }


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.float32, window: Optional[int] = None) -> Params:
    """KV cache. Sliding-window layers allocate a RING BUFFER of ``window``
    slots instead of ``max_len`` — at long_500k this shrinks a local layer's
    cache by seq/window (512× for gemma3's 1024-token local layers)."""
    hd = cfg.resolved_head_dim
    slots = min(max_len, window) if window else max_len
    return {
        "k": jnp.zeros((batch, slots, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, slots, cfg.n_kv_heads, hd), dtype),
    }


def apply_attention(
    p: Params,
    cfg: ModelConfig,
    spec: LayerSpec,
    x: jnp.ndarray,  # [B, S, d]
    *,
    pos_offset: jnp.ndarray | int = 0,
    cache: Optional[Params] = None,
    kv_chunk: int = 1024,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Self-attention. With ``cache`` the new K/V are written at
    ``pos_offset`` and attention runs over the whole cache (prefill when
    S>1, decode when S==1); without it, attention is over ``x`` only."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    theta = spec.rope_theta or cfg.rope_theta
    q = linear(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = linear(p["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
    v = linear(p["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)
    q_pos = jnp.asarray(pos_offset, jnp.int32) + jnp.arange(S, dtype=jnp.int32)
    q = apply_rope(q, q_pos, theta)
    k = apply_rope(k, q_pos, theta)

    if cache is None:
        out = attend(q, k, v, q_pos=q_pos, kv_pos=q_pos, window=spec.window, kv_chunk=kv_chunk)
        new_cache = None
    else:
        off = jnp.asarray(pos_offset, jnp.int32)
        Smax = cache["k"].shape[1]
        if spec.window is not None and Smax == spec.window:
            # ring buffer: slot(p) = p % w. Attention runs over the PREVIOUS
            # ring contents (context positions off-w..off-1; unwritten slots
            # mask out) plus the fresh block, THEN the last min(S, w) new
            # tokens are written into their (unique) slots.
            w = spec.window
            s_idx = jnp.arange(w, dtype=jnp.int32)
            last_old = off - 1
            pos_old = last_old - jnp.mod(last_old - s_idx, w)
            pos_old = jnp.where(pos_old < 0, jnp.iinfo(jnp.int32).max, pos_old)
            k_ctx = jnp.concatenate([cache["k"].astype(k.dtype), k], axis=1)
            v_ctx = jnp.concatenate([cache["v"].astype(v.dtype), v], axis=1)
            kv_pos = jnp.concatenate([pos_old, q_pos])
            out = attend(q, k_ctx, v_ctx, q_pos=q_pos, kv_pos=kv_pos, window=w,
                         kv_chunk=kv_chunk)
            kw = k if S <= w else k[:, S - w:]
            vw = v if S <= w else v[:, S - w:]
            n = kw.shape[1]
            slots = jnp.mod(off + S - n + jnp.arange(n, dtype=jnp.int32), w)
            ck = cache["k"].at[:, slots].set(kw.astype(cache["k"].dtype))
            cv = cache["v"].at[:, slots].set(vw.astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv}
        else:
            ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, off, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, off, 0, 0))
            new_cache = {"k": ck, "v": cv}
            if spec.window is not None and S == 1 and Smax > spec.window:
                # decode with sliding window over a full-length cache
                w = spec.window
                start = jnp.clip(off - w + 1, 0, Smax - w)
                ks = jax.lax.dynamic_slice_in_dim(ck, start, w, axis=1)
                vs = jax.lax.dynamic_slice_in_dim(cv, start, w, axis=1)
                kv_pos = start + jnp.arange(w, dtype=jnp.int32)
                out = attend(q, ks, vs, q_pos=q_pos, kv_pos=kv_pos, window=w, kv_chunk=kv_chunk)
            else:
                kv_pos = jnp.arange(Smax, dtype=jnp.int32)
                out = attend(q, ck, cv, q_pos=q_pos, kv_pos=kv_pos, window=spec.window, kv_chunk=kv_chunk)

    y = linear(p["wo"], out.reshape(B, S, cfg.n_heads * hd))
    return y, new_cache
