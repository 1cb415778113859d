"""Pallas TPU fused DP-SGD clip-and-accumulate kernels (paper Eq. 7 inner
loop). Per-example gradients are flattened to 1-D; two kernels cover the
hot path:

* ``sumsq``           — blockwise partial sum-of-squares (norm computation),
* ``scale_accumulate``— acc += g * scale with the scalar scale in SMEM,

so one DP microbatch step streams each gradient chunk HBM→VMEM exactly once
per pass instead of materializing clipped copies (the fusion GPU DP-SGD
gets from apex-style multi-tensor kernels; here it is explicit VMEM
blocking on the VPU).

Layout: a flat vector is padded and viewed as ``[rows, 128]`` (lanes last)
and blocked by ``[block_rows, 128]`` with ``block_rows`` a multiple of 8,
so every block is whole (8, 128) vreg tiles. That is what Mosaic accepts
both for a single vector and under ``vmap`` (the engine vmaps the DP step
over clients, which prepends a batch dim to every block). Scalars ride in
SMEM as a ``[1, n]`` row for the same reason: a batched ``[n]`` SMEM
operand is refused, a batched ``[1, n]`` one is not.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

LANES, SUBLANES = 128, 8


def row_layout(n: int, block: int) -> Tuple[int, int]:
    """``(block_rows, n_blocks)`` of the ``[rows, 128]`` view of an
    ``n``-vector blocked by about ``block`` elements per grid step."""
    rows = -(-max(n, 1) // LANES)
    rows = -(-rows // SUBLANES) * SUBLANES
    br = min(max(block // LANES // SUBLANES, 1) * SUBLANES, rows)
    return br, -(-rows // br)


def to_rows(x: jnp.ndarray, br: int, n_blocks: int) -> jnp.ndarray:
    """Pad the 1-D ``x`` with zeros to ``n_blocks·br·128`` and view it as
    ``[n_blocks·br, 128]``."""
    total = n_blocks * br * LANES
    if total != x.shape[0]:
        x = jnp.pad(x, (0, total - x.shape[0]))
    return x.reshape(n_blocks * br, LANES)


def row_spec(br: int) -> pl.BlockSpec:
    return pl.BlockSpec((br, LANES), lambda i: (i, 0))


def _sumsq_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    sq = (x * x).reshape(-1, SUBLANES, LANES)
    o_ref[...] = jnp.sum(sq, axis=0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sumsq(x: jnp.ndarray, *, block: int = 65536,
          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sum of squares of a 1-D vector (f32 accumulation). Each grid step
    reduces its block to one (8, 128) tile of partial sums; the tiles are
    summed outside the kernel."""
    br, n_blocks = row_layout(x.shape[0], block)
    partial_sums = pl.pallas_call(
        _sumsq_kernel,
        grid=(n_blocks,),
        in_specs=[row_spec(br)],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * SUBLANES, LANES),
                                       jnp.float32),
        interpret=resolve_interpret(interpret),
    )(to_rows(x, br, n_blocks))
    return jnp.sum(partial_sums)


def _scale_acc_kernel(scale_ref, acc_ref, g_ref, o_ref):
    s = scale_ref[0, 0]
    o_ref[...] = acc_ref[...] + g_ref[...].astype(jnp.float32) * s


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def scale_accumulate(acc: jnp.ndarray, g: jnp.ndarray, scale: jnp.ndarray,
                     *, block: int = 65536,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """acc + g * scale for 1-D f32 acc / any-dtype g, blockwise."""
    n = acc.shape[0]
    br, n_blocks = row_layout(n, block)
    out = pl.pallas_call(
        _scale_acc_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # [1, 1] scale
            row_spec(br),
            row_spec(br),
        ],
        out_specs=row_spec(br),
        out_shape=jax.ShapeDtypeStruct((n_blocks * br, LANES), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(jnp.reshape(scale, (1, 1)).astype(jnp.float32),
      to_rows(acc, br, n_blocks), to_rows(g, br, n_blocks))
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("clip_norm", "block", "interpret"))
def clip_accumulate(acc: jnp.ndarray, g: jnp.ndarray, clip_norm: float,
                    *, block: int = 65536,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """One per-example DP-SGD update of the gradient accumulator:
    acc += g / max(1, ||g||/C)  — Eq. (7) clip + sum, fused."""
    norm = jnp.sqrt(sumsq(g, block=block, interpret=interpret))
    scale = 1.0 / jnp.maximum(1.0, norm / clip_norm)
    return scale_accumulate(acc, g, scale, block=block, interpret=interpret)
