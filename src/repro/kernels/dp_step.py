"""Pallas TPU fused DP noise-add + optimizer step kernels (tail of the
Eq. 7 chain).

After the per-example clip+accumulate scan (``dp_clip``), the plain-XLA
path still walks the full gradient/parameter set through HBM several more
times: noise add, clipped-mean divide, weight decay, moment updates and
the parameter step each run as separate ``tree_map`` passes. These kernels
fuse that tail so each gradient chunk is streamed HBM→VMEM once:

* :func:`noise_sgd_step`  — p' = p − lr·((acc + σ·noise)/n + wd·p)
* :func:`noise_adam_step` — the same fused chain through Adam's moment
  updates and bias-corrected step; returns (p', m', v').

The Gaussian noise vector is generated OUTSIDE (``jax.random.normal`` is
already a fused XLA kernel, and drawing it per parameter leaf with the
same key-split schedule as ``repro.core.dp.add_gaussian_noise`` keeps the
noise values identical to the unfused path — see
``repro.core.dp._flat_gaussian_like``); the kernels fuse all arithmetic
after the draw. Scalars ride in SMEM; b1/b2/eps are trace-time constants
(optimizer hyperparameters, fixed per compiled step). All math is f32 —
the fused path is gated to f32 params/moments by the caller
(``repro.core.dp.dp_adam_update``), matching Adam's f32 update path
exactly, so parity with the unfused chain is elementwise.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret
from .dp_clip import LANES, row_layout, row_spec, to_rows


def _sgd_kernel(sc_ref, acc_ref, noise_ref, p_ref, p2_ref):
    stddev, n_units, lr, wd = (sc_ref[0, 0], sc_ref[0, 1], sc_ref[0, 2],
                               sc_ref[0, 3])
    g = (acc_ref[...] + stddev * noise_ref[...]) / n_units
    p = p_ref[...].astype(jnp.float32)
    g = g + wd * p
    p2_ref[...] = (p - lr * g).astype(p2_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def noise_sgd_step(acc: jnp.ndarray, noise: jnp.ndarray, p: jnp.ndarray, *,
                   stddev, n_units, lr, weight_decay=0.0, block: int = 65536,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused noise-add + clipped-mean + SGD step over 1-D flat vectors:
    ``p − lr·((acc + stddev·noise)/n_units + weight_decay·p)``."""
    n = acc.shape[0]
    br, n_blocks = row_layout(n, block)
    sc = jnp.stack([jnp.asarray(s, jnp.float32)
                    for s in (stddev, n_units, lr, weight_decay)])[None]
    out = pl.pallas_call(
        _sgd_kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]  # [1, n] scalars
        + [row_spec(br)] * 3,
        out_specs=row_spec(br),
        out_shape=jax.ShapeDtypeStruct((n_blocks * br, LANES), p.dtype),
        interpret=resolve_interpret(interpret),
    )(sc, *(to_rows(x, br, n_blocks) for x in (acc, noise, p)))
    return out.reshape(-1)[:n]


def _adam_kernel(b1, b2, eps, sc_ref, acc_ref, noise_ref, p_ref, m_ref,
                 v_ref, p2_ref, m2_ref, v2_ref):
    stddev, n_units, lr = sc_ref[0, 0], sc_ref[0, 1], sc_ref[0, 2]
    wd, c1, c2 = sc_ref[0, 3], sc_ref[0, 4], sc_ref[0, 5]
    g = (acc_ref[...] + stddev * noise_ref[...]) / n_units
    p = p_ref[...].astype(jnp.float32)
    g = g + wd * p
    m2 = b1 * m_ref[...].astype(jnp.float32) + (1.0 - b1) * g
    v2 = b2 * v_ref[...].astype(jnp.float32) + (1.0 - b2) * g * g
    step = lr * (m2 / c1) / (jnp.sqrt(v2 / c2) + eps)
    p2_ref[...] = (p - step).astype(p2_ref.dtype)
    m2_ref[...] = m2.astype(m2_ref.dtype)
    v2_ref[...] = v2.astype(v2_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "eps", "block", "interpret"))
def noise_adam_step(acc: jnp.ndarray, noise: jnp.ndarray, p: jnp.ndarray,
                    m: jnp.ndarray, v: jnp.ndarray, *, stddev, n_units, lr,
                    weight_decay=0.0, b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8, c1=None, c2=None, block: int = 65536,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused noise-add + clipped-mean + Adam step over 1-D flat vectors.

    ``c1``/``c2`` are the bias corrections ``1 − b1**t`` / ``1 − b2**t``
    for the POST-update step count t (runtime scalars — they depend on the
    traced step counter). Returns ``(p', m', v')`` with the exact update
    chain of :class:`repro.optim.optimizers.Adam` on the noisy clipped
    mean gradient ``(acc + stddev·noise)/n_units (+ weight_decay·p)``."""
    assert c1 is not None and c2 is not None, "pass bias corrections c1/c2"
    n = acc.shape[0]
    br, n_blocks = row_layout(n, block)
    sc = jnp.stack([jnp.asarray(s, jnp.float32)
                    for s in (stddev, n_units, lr, weight_decay, c1, c2)])[None]
    outs = pl.pallas_call(
        functools.partial(_adam_kernel, float(b1), float(b2), float(eps)),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]  # [1, n] scalars
        + [row_spec(br)] * 5,
        out_specs=(row_spec(br),) * 3,
        out_shape=tuple(jax.ShapeDtypeStruct((n_blocks * br, LANES), x.dtype)
                        for x in (p, m, v)),
        interpret=resolve_interpret(interpret),
    )(sc, *(to_rows(x, br, n_blocks) for x in (acc, noise, p, m, v)))
    return tuple(x.reshape(-1)[:n] for x in outs)
