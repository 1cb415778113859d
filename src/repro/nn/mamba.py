"""Mamba-1 selective-SSM block (falcon-mamba / jamba).

Training/prefill uses a chunked selective scan: an outer ``lax.scan`` over
sequence chunks carries the [B, d_inner, d_state] recurrent state, and the
in-chunk recurrence is a work-efficient ``associative_scan``. Only one
chunk's [B, C, d_inner, d_state] tensor is live at a time, which is the
TPU adaptation of the paper-standard CUDA selective-scan kernel (the Pallas
version of the same chunking lives in ``repro.kernels.mamba_scan``).
Decode is the O(1) single-step recurrence with a rolling conv state.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import MambaConfig, ModelConfig
from .modules import Params, init_linear, linear, normal_init


def init_mamba(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    m = cfg.mamba or MambaConfig()
    d, di, ds, dtr = cfg.d_model, cfg.d_inner, m.d_state, cfg.resolved_dt_rank
    k = jax.random.split(key, 5)
    # S4D-real initialization for A; dt bias so softplus(dt) starts ~1e-3..1e-1
    A = jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))
    return {
        "in_proj": init_linear(k[0], d, 2 * di, dtype=dtype),
        "conv_w": normal_init(k[1], (m.d_conv, di), 0.2, dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": init_linear(k[2], di, dtr + 2 * ds, dtype=dtype),
        "dt_proj": init_linear(k[3], dtr, di, bias=True, scale=dtr**-0.5, dtype=dtype),
        "A_log": jnp.log(A).astype(dtype),
        "D": jnp.ones((di,), dtype),
        "out_proj": init_linear(k[4], di, d, dtype=dtype),
    }


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> Params:
    m = cfg.mamba or MambaConfig()
    return {
        "conv": jnp.zeros((batch, m.d_conv - 1, cfg.d_inner), dtype),
        "ssm": jnp.zeros((batch, cfg.d_inner, m.d_state), jnp.float32),
    }


def _causal_conv(p: Params, x: jnp.ndarray, prev: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Depthwise causal conv along seq. x:[B,S,di]; prev:[B,d_conv-1,di]."""
    K = p["conv_w"].shape[0]
    if prev is None:
        prev = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    y = sum(xp[:, i : i + S, :] * p["conv_w"][i] for i in range(K))
    return y + p["conv_b"]


def _ssm_inputs(p: Params, cfg: ModelConfig, xc: jnp.ndarray):
    """From conv output xc:[B,S,di] compute (dt, B, C, A) in float32."""
    m = cfg.mamba or MambaConfig()
    dtr = cfg.resolved_dt_rank
    dbc = linear(p["x_proj"], xc)
    dt_r = dbc[..., :dtr]
    Bm = dbc[..., dtr : dtr + m.d_state].astype(jnp.float32)
    Cm = dbc[..., dtr + m.d_state :].astype(jnp.float32)
    dt = jax.nn.softplus(linear(p["dt_proj"], dt_r).astype(jnp.float32) - 4.0)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    return dt, Bm, Cm, A


def _chunk_scan(h0, dt, A, Bm, Cm, xc):
    """One chunk of the selective scan.

    h0:[B,di,ds] f32; dt,xc:[B,C,di]; Bm,Cm:[B,C,ds]; A:[di,ds].
    Returns (y [B,C,di] f32, h_last [B,di,ds]).
    """
    a = jnp.exp(dt[..., None] * A)  # [B,C,di,ds]
    b = (dt * xc.astype(jnp.float32))[..., None] * Bm[:, :, None, :]

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    a_cum, b_cum = jax.lax.associative_scan(combine, (a, b), axis=1)
    h = a_cum * h0[:, None] + b_cum  # [B,C,di,ds]
    y = jnp.einsum("bcds,bcs->bcd", h, Cm)
    return y, h[:, -1]


def apply_mamba(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,  # [B, S, d]
    *,
    cache: Optional[Params] = None,
    chunk: int = 256,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    m = cfg.mamba or MambaConfig()
    B, S, _ = x.shape
    di = cfg.d_inner
    xz = linear(p["in_proj"], x)
    x1, z = xz[..., :di], xz[..., di:]

    prev_conv = cache["conv"] if cache is not None else None
    xc = jax.nn.silu(_causal_conv(p, x1, prev_conv))
    dt, Bm, Cm, A = _ssm_inputs(p, cfg, xc)
    h0 = cache["ssm"] if cache is not None else jnp.zeros((B, di, m.d_state), jnp.float32)

    if S == 1:
        # decode: single recurrence step
        a = jnp.exp(dt[:, 0, :, None] * A)
        b = (dt[:, 0] * xc[:, 0].astype(jnp.float32))[..., None] * Bm[:, 0, None, :]
        h = a * h0 + b
        y = jnp.einsum("bds,bs->bd", h, Cm[:, 0])[:, None, :]
        h_last = h
    elif S <= chunk:
        y, h_last = _chunk_scan(h0, dt, A, Bm, Cm, xc)
    else:
        n = -(-S // chunk)
        pad = n * chunk - S
        if pad:
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))  # dt=0 -> identity step
            Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
            Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
            xcp = jnp.pad(xc, ((0, 0), (0, pad), (0, 0)))
        else:
            xcp = xc
        resh = lambda t: t.reshape(B, n, chunk, *t.shape[2:]).swapaxes(0, 1)

        def body(h, xs):
            dtc, bc, cc, xcc = xs
            y, h_next = _chunk_scan(h, dtc, A, bc, cc, xcc)
            return h_next, y

        h_last, ys = jax.lax.scan(body, h0,
                                  (resh(dt), resh(Bm), resh(Cm), resh(xcp)))
        y = ys.swapaxes(0, 1).reshape(B, n * chunk, di)[:, :S]

    y = y + p["D"].astype(jnp.float32) * xc.astype(jnp.float32)
    out = linear(p["out_proj"], (y.astype(x.dtype) * jax.nn.silu(z)))

    new_cache = None
    if cache is not None:
        K = m.d_conv
        if S >= K - 1:
            conv_state = x1[:, S - (K - 1) :, :]
        else:
            conv_state = jnp.concatenate([cache["conv"][:, S:, :], x1], axis=1)
        new_cache = {"conv": conv_state.astype(cache["conv"].dtype), "ssm": h_last}
    return out, new_cache
