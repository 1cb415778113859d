"""Pallas TPU fused PushSum gossip kernels (Algorithm 1 lines 7-11).

The stacked backends hold the cohort's flattened proxies as one [K, D]
array. Plain XLA runs the exchange as separate matmuls plus a de-bias
divide — each walking the full K·D parameter set through HBM. These
kernels block over D and keep the tiny [K, K] exchange matrix and the [K]
weight vectors resident in VMEM, so every parameter chunk is streamed
HBM→VMEM exactly once per round:

* :func:`fused_pushsum_mix` — the SYNCHRONOUS exchange on de-biased
  values z (what ``FederationEngine._round_core`` mixes):
  out = P·z (optionally fused-de-biased by w' = P·w), w' = P·w.
* :func:`fused_stale_mix` — the async τ>0 exchange of
  ``repro.core.gossip.stale_gossip_reference``: re-bias θ = z·w, emit the
  off-diagonal send ``sent @ θ``, merge ``kept·θ`` with the delayed
  delivery, and de-bias by the identically-delayed weights — two outputs
  (z', send) per chunk, one pass.

Accumulation is f32 (``preferred_element_type``) regardless of the input
dtype; the [K]-sized weight reductions are computed outside the kernel
(they are O(K), not O(K·D)). The chunk width shrinks with K
(:func:`mix_block`) so that the double-buffered [K, b] blocks fit the
scoped VMEM at any cohort size. Numeric contract: allclose to the plain-XLA
chain (same math, different reduction order) — pinned by the ``use_pallas``
columns of tests/test_conformance.py and the ``ref.py`` oracle sweeps.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

#: scoped-VMEM bytes the streamed [K, b] blocks and their f32 temporaries
#: may take: half of the 16 MiB default scoped limit of a TPU v5e core,
#: leaving the rest to the resident [K, K] matrix and Mosaic's own scratch
VMEM_BLOCK_BUDGET = 8 * 2 ** 20

#: f32 matmuls at full precision (a TPU's default is one bf16 pass)
_HIGHEST = jax.lax.Precision.HIGHEST


def mix_block(K: int, itemsize: int, n_streams: int, n_temps: int,
              block: int) -> int:
    """Chunk width ``b`` of a [K, D] mix kernel: at most ``block``, and the
    widest multiple of 128 whose ``n_streams`` double-buffered [K, b]
    input/output blocks plus ``n_temps`` f32 [K, b] temporaries fit
    :data:`VMEM_BLOCK_BUDGET` (8192 up to K≈40 for f32, 1280 at K=256)."""
    per_col = K * (2 * n_streams * itemsize + 4 * n_temps)
    fit = max(128, VMEM_BLOCK_BUDGET // per_col // 128 * 128)
    return min(block, fit)


def _mix_kernel(debias: bool, P_ref, w2_ref, x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                       # [K, b]
    mixed = jnp.dot(P_ref[...], x, preferred_element_type=jnp.float32,
                    precision=_HIGHEST)
    if debias:
        mixed = mixed / w2_ref[...][:, None]
    o_ref[...] = mixed.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("debias", "block", "interpret"))
def fused_pushsum_mix(flat: jnp.ndarray, w: jnp.ndarray, P: jnp.ndarray, *,
                      debias: bool = True, block: int = 8192,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One synchronous PushSum exchange over stacked [K, D] client vectors.

    Returns ``(P·flat / (P·w)[:, None], P·w)`` with ``debias=True`` (the
    engine's stacked round) or ``(P·flat, P·w)`` with ``debias=False``
    (the raw :func:`repro.core.gossip.pushsum_mix` contract). ``P`` stays
    resident in VMEM across the D-grid; w' is O(K) and computed outside."""
    K, D = flat.shape
    Pf = jnp.asarray(P, jnp.float32)
    w2 = jnp.matmul(Pf, w.astype(jnp.float32), precision=_HIGHEST)
    b = min(mix_block(K, flat.dtype.itemsize, 2, 2, block), max(D, 1))
    n_blocks = -(-D // b)
    pad = n_blocks * b - D
    x = jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat
    out = pl.pallas_call(
        functools.partial(_mix_kernel, debias),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((K, K), lambda i: (0, 0)),  # P resident
            pl.BlockSpec((K,), lambda i: (0,)),      # w' resident
            pl.BlockSpec((K, b), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((K, b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((K, n_blocks * b), flat.dtype),
        interpret=resolve_interpret(interpret),
    )(Pf, w2, x)
    return out[:, :D], w2.astype(w.dtype)


def _stale_kernel(w_ref, kept_ref, sent_ref, w2_ref, x_ref, buf_ref,
                  z_ref, send_ref):
    theta = x_ref[...].astype(jnp.float32) * w_ref[...][:, None]  # re-bias
    send = jnp.dot(sent_ref[...], theta, preferred_element_type=jnp.float32,
                   precision=_HIGHEST)
    mixed = kept_ref[...][:, None] * theta + buf_ref[...].astype(jnp.float32)
    z_ref[...] = (mixed / w2_ref[...][:, None]).astype(z_ref.dtype)
    send_ref[...] = send.astype(send_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_stale_mix(flat: jnp.ndarray, w: jnp.ndarray, kept: jnp.ndarray,
                    sent: jnp.ndarray, buf_t0: jnp.ndarray,
                    buf_w0: jnp.ndarray, *, block: int = 8192,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                               jnp.ndarray]:
    """One stale (async τ>0) exchange: returns ``(z', send_t, w', send_w)``.

    ``flat``/``w`` are the [K, D] de-biased proxies and weights; ``kept``
    [K] / ``sent`` [K, K] the diag/off-diag split of P^(t)
    (:func:`repro.core.gossip.stale_mix_split`); ``buf_t0``/``buf_w0`` the
    delivery rotating out of the τ-deep in-flight buffer. The caller owns
    the buffer rotation (``send_t``/``send_w`` are pushed in). Per chunk
    the kernel re-biases θ = z·w, computes both the kept-merge and the
    send matmul, and de-biases — one HBM→VMEM pass for two outputs."""
    K, D = flat.shape
    wf = w.astype(jnp.float32)
    keptf = kept.astype(jnp.float32)
    sentf = sent.astype(jnp.float32)
    w2 = keptf * wf + buf_w0.astype(jnp.float32)
    send_w = jnp.matmul(sentf, wf, precision=_HIGHEST)
    b = min(mix_block(K, flat.dtype.itemsize, 4, 4, block), max(D, 1))
    n_blocks = -(-D // b)
    pad = n_blocks * b - D
    x, buf = flat, buf_t0
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        buf = jnp.pad(buf, ((0, 0), (0, pad)))
    z2, send_t = pl.pallas_call(
        _stale_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((K,), lambda i: (0,)),      # w resident
            pl.BlockSpec((K,), lambda i: (0,)),      # kept resident
            pl.BlockSpec((K, K), lambda i: (0, 0)),  # sent resident
            pl.BlockSpec((K,), lambda i: (0,)),      # w' resident
            pl.BlockSpec((K, b), lambda i: (0, i)),
            pl.BlockSpec((K, b), lambda i: (0, i)),
        ],
        out_specs=(pl.BlockSpec((K, b), lambda i: (0, i)),
                   pl.BlockSpec((K, b), lambda i: (0, i))),
        out_shape=(jax.ShapeDtypeStruct((K, n_blocks * b), flat.dtype),
                   jax.ShapeDtypeStruct((K, n_blocks * b), flat.dtype)),
        interpret=resolve_interpret(interpret),
    )(wf, keptf, sentf, w2, x, buf)
    return (z2[:, :D], send_t[:, :D], w2.astype(w.dtype),
            send_w.astype(w.dtype))
