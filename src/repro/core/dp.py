"""DP-SGD (Abadi et al. 2016) — paper Eq. (7).

Per-example gradients are computed with an O(1)-memory ``lax.scan`` over the
batch (TPU adaptation: GPU DP-SGD implementations vmap the whole batch,
which multiplies gradient memory by B; sequentializing keeps the same FLOPs
with one live gradient pytree). Each per-example gradient is clipped to L2
norm C, the clipped gradients are summed, and Gaussian noise N(0, σ²C²) is
added once to the sum before dividing by B — exactly Eq. (7).

``microbatch`` > 1 trades memory for speed by treating groups of examples
as one DP unit (sensitivity then covers the group — guarantee becomes
per-group; keep 1 for per-example guarantees).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.dp_clip import scale_accumulate, sumsq
from ..kernels.dp_step import noise_adam_step
from ..nn.modules import tree_flatten_vector, tree_unflatten_vector

Params = Any


def clip_by_global_norm(tree: Params, max_norm: float) -> Tuple[Params, jnp.ndarray]:
    leaves = jax.tree_util.tree_leaves(tree)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))
    scale = 1.0 / jnp.maximum(1.0, norm / max_norm)
    # the scale is applied in f32 and the PRODUCT cast back: casting the
    # scale itself to a low-precision leaf dtype rounds it (bf16 has ~3
    # significant digits), and an upward-rounded scale leaves the clipped
    # tree ABOVE the sensitivity bound C the DP guarantee assumes
    clipped = jax.tree_util.tree_map(
        lambda x: (x.astype(jnp.float32) * scale).astype(x.dtype), tree)
    return clipped, norm


def add_gaussian_noise(tree: Params, key, stddev: float) -> Params:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    noisy = [
        (x.astype(jnp.float32) + stddev * jax.random.normal(k, x.shape, jnp.float32)).astype(x.dtype)
        for x, k in zip(leaves, keys)
    ]
    return jax.tree_util.tree_unflatten(treedef, noisy)


def _flat_gaussian_like(tree: Params, key) -> jnp.ndarray:
    """The N(0,1) draws of :func:`add_gaussian_noise`, concatenated flat.

    Same per-leaf key-split schedule and per-leaf shapes, so the noise
    VALUES are identical to the tree-structured path — the fused flat
    chain differs from the unfused one only in arithmetic order, never in
    randomness (what keeps the use_pallas conformance columns allclose)."""
    leaves = jax.tree_util.tree_leaves(tree)
    keys = jax.random.split(key, len(leaves))
    return jnp.concatenate([
        jax.random.normal(k, x.shape, jnp.float32).reshape(-1)
        for x, k in zip(leaves, keys)])


def dp_gradient(
    loss_fn: Callable[[Params, Any], jnp.ndarray],
    params: Params,
    batch: Any,  # pytree whose leaves have leading batch dim B
    key,
    *,
    clip_norm: float,
    noise_multiplier: float,
    microbatch: int = 1,
    vectorized: bool = False,
    use_pallas: bool = False,
    interpret: Optional[bool] = None,
) -> Tuple[Params, dict]:
    """Noisy clipped mean gradient per Eq. (7). Returns (grad, metrics).

    ``vectorized=True`` vmaps the per-example gradients (O(B) gradient
    memory — fine for the paper's CNN-scale models, much faster); the
    default scan path is O(1) in B and is what the LLM-scale path uses.

    ``use_pallas=True`` runs the scan path's clip+accumulate over a
    FLATTENED gradient vector through the fused ``repro.kernels.dp_clip``
    kernels (``sumsq`` for the norm, ``scale_accumulate`` for both the
    clipped sum and the noise add), so each gradient chunk is streamed
    HBM→VMEM once per pass. Noise draws reuse the per-leaf key schedule
    of :func:`add_gaussian_noise` (identical values); results are
    allclose to the plain path (reduction-order-only divergence). The
    vectorized path ignores the flag (its einsum is already one fused
    contraction). ``interpret`` forwards to the kernels (None = platform
    autodetect)."""
    B = jax.tree_util.tree_leaves(batch)[0].shape[0]
    assert B % microbatch == 0, (B, microbatch)
    n_units = B // microbatch
    grad_fn = jax.value_and_grad(loss_fn)

    if vectorized:
        units = jax.tree_util.tree_map(
            lambda x: x.reshape((n_units, microbatch) + x.shape[1:]), batch)
        losses, grads = jax.vmap(grad_fn, in_axes=(None, 0))(params, units)
        norms = jax.vmap(lambda g: jnp.sqrt(sum(
            jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree_util.tree_leaves(g))))(grads)
        scales = 1.0 / jnp.maximum(1.0, norms / clip_norm)
        acc = jax.tree_util.tree_map(
            lambda g: jnp.einsum("b...,b->...", g.astype(jnp.float32), scales), grads)
        noisy = add_gaussian_noise(acc, key, noise_multiplier * clip_norm)
        grad = jax.tree_util.tree_map(lambda x: x / n_units, noisy)
        return grad, {"loss": jnp.mean(losses), "mean_grad_norm": jnp.mean(norms)}

    def unit(i):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, i * microbatch, microbatch, 0),
            batch,
        )

    zero = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), params)

    if use_pallas:
        def body(carry, i):
            acc, loss_sum, norm_sum = carry
            loss, g = grad_fn(params, unit(i))
            gf = tree_flatten_vector(g)
            norm = jnp.sqrt(sumsq(gf, interpret=interpret))
            scale = 1.0 / jnp.maximum(1.0, norm / clip_norm)
            acc = scale_accumulate(acc, gf, scale, interpret=interpret)
            return (acc, loss_sum + loss, norm_sum + norm), None

        acc0 = tree_flatten_vector(zero)
        (acc, loss_sum, norm_sum), _ = jax.lax.scan(
            body, (acc0, jnp.zeros(()), jnp.zeros(())), jnp.arange(n_units))
        # noise add via the same kernel: acc + noise * (σ·C), one pass
        noise = _flat_gaussian_like(zero, key)
        stddev = jnp.asarray(noise_multiplier * clip_norm, jnp.float32)
        noisy = scale_accumulate(acc, noise, stddev, interpret=interpret)
        grad = tree_unflatten_vector(noisy / n_units, zero)
    else:
        def body(carry, i):
            acc, loss_sum, norm_sum = carry
            loss, g = grad_fn(params, unit(i))
            g_clip, norm = clip_by_global_norm(g, clip_norm)
            acc = jax.tree_util.tree_map(
                lambda a, x: a + x.astype(jnp.float32), acc, g_clip)
            return (acc, loss_sum + loss, norm_sum + norm), None

        (acc, loss_sum, norm_sum), _ = jax.lax.scan(
            body, (zero, jnp.zeros(()), jnp.zeros(())), jnp.arange(n_units))
        noisy = add_gaussian_noise(acc, key, noise_multiplier * clip_norm)
        grad = jax.tree_util.tree_map(lambda x: x / n_units, noisy)

    metrics = {
        "loss": loss_sum / n_units,
        "mean_grad_norm": norm_sum / n_units,
    }
    return grad, metrics


def dp_adam_update(
    loss_fn: Callable[[Params, Any], jnp.ndarray],
    params: Params,
    opt_state,
    batch: Any,
    key,
    *,
    opt,
    clip_norm: float,
    noise_multiplier: float,
    microbatch: int = 1,
    interpret: Optional[bool] = None,
) -> Tuple[Params, Any, dict]:
    """Fully fused DP-SGD + Adam step: Eq. (7) clip→noise and the
    optimizer update as ONE kernel chain over flat vectors.

    The per-unit scan clips and accumulates through the ``dp_clip``
    kernels, then :func:`repro.kernels.dp_step.noise_adam_step` applies
    noise-add, clipped-mean divide, weight decay, moment updates and the
    bias-corrected parameter step in a single HBM→VMEM pass — the tail
    the unfused path spreads over six ``tree_map`` sweeps. Returns
    ``(params', opt_state', metrics)`` with the same metrics dict as
    :func:`dp_gradient`.

    The fused elementwise chain is exact only for the optimizer's f32
    update path, so non-f32 params, master weights (``p32``) or non-f32
    moments fall back to ``dp_gradient(use_pallas=True)`` + ``opt.update``
    (still kernel-clipped, tree-structured step). ``opt`` must be a
    :class:`repro.optim.optimizers.Adam`."""
    from ..optim.optimizers import AdamState

    assert isinstance(opt_state, AdamState), type(opt_state)
    fusable = (
        opt_state.p32 is None
        and jnp.dtype(opt.moment_dtype) == jnp.float32
        and all(x.dtype == jnp.float32
                for x in jax.tree_util.tree_leaves(params)))
    if not fusable:
        grad, metrics = dp_gradient(
            loss_fn, params, batch, key, clip_norm=clip_norm,
            noise_multiplier=noise_multiplier, microbatch=microbatch,
            use_pallas=True, interpret=interpret)
        params2, opt2 = opt.update(grad, opt_state, params)
        return params2, opt2, metrics

    B = jax.tree_util.tree_leaves(batch)[0].shape[0]
    assert B % microbatch == 0, (B, microbatch)
    n_units = B // microbatch
    grad_fn = jax.value_and_grad(loss_fn)

    def unit(i):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, i * microbatch,
                                                   microbatch, 0), batch)

    def body(carry, i):
        acc, loss_sum, norm_sum = carry
        loss, g = grad_fn(params, unit(i))
        gf = tree_flatten_vector(g)
        norm = jnp.sqrt(sumsq(gf, interpret=interpret))
        scale = 1.0 / jnp.maximum(1.0, norm / clip_norm)
        acc = scale_accumulate(acc, gf, scale, interpret=interpret)
        return (acc, loss_sum + loss, norm_sum + norm), None

    p_flat = tree_flatten_vector(params)
    (acc, loss_sum, norm_sum), _ = jax.lax.scan(
        body, (jnp.zeros_like(p_flat), jnp.zeros(()), jnp.zeros(())),
        jnp.arange(n_units))

    noise = _flat_gaussian_like(params, key)
    t2 = opt_state.t + 1
    tf = t2.astype(jnp.float32)
    p2, m2, v2 = noise_adam_step(
        acc, noise, p_flat,
        tree_flatten_vector(opt_state.m), tree_flatten_vector(opt_state.v),
        stddev=noise_multiplier * clip_norm, n_units=n_units, lr=opt.lr,
        weight_decay=opt.weight_decay, b1=opt.b1, b2=opt.b2, eps=opt.eps,
        c1=1 - opt.b1 ** tf, c2=1 - opt.b2 ** tf, interpret=interpret)
    params2 = tree_unflatten_vector(p2, params)
    opt2 = AdamState(tree_unflatten_vector(m2, opt_state.m),
                     tree_unflatten_vector(v2, opt_state.v), t2, None)
    metrics = {
        "loss": loss_sum / n_units,
        "mean_grad_norm": norm_sum / n_units,
    }
    return params2, opt2, metrics


def dp_gradient_chunked(
    loss_fn: Callable[[Params, Any], jnp.ndarray],
    params: Params,
    batch: Any,
    key,
    *,
    clip_norm: float,
    noise_multiplier: float,
    chunk: int = 8,
    prepare_chunk: Callable[[Any], Any] = lambda b: b,
) -> Tuple[Params, dict]:
    """Per-example DP-SGD gradient (Eq. 7) with a scan-of-vmap schedule:
    scan over B/chunk chunks, vmap the per-example grads inside each chunk.
    Identical semantics to ``dp_gradient`` (every example clipped
    individually); ``chunk`` trades peak gradient memory (chunk × |θ|)
    against scan trip count — the knob the §Perf loop tunes on TPU.

    ``prepare_chunk`` runs ONCE per chunk, outside the per-example vmap.
    The ProxyFL step passes the private peer logits as a batch leaf when
    its private step made them for the whole batch; when that step runs in
    microbatches it uses this hook to compute them (θ-independent) with one
    batched forward per chunk instead of once per example."""
    B = jax.tree_util.tree_leaves(batch)[0].shape[0]
    assert B % chunk == 0, (B, chunk)
    n_chunks = B // chunk
    grad_fn = jax.value_and_grad(lambda p, ex: loss_fn(
        p, jax.tree_util.tree_map(lambda x: x[None], ex)))

    def per_chunk(i):
        return prepare_chunk(jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, 0), batch))

    def body(carry, i):
        acc, loss_sum, norm_sum = carry
        losses, grads = jax.vmap(grad_fn, in_axes=(None, 0))(params, per_chunk(i))
        norms = jax.vmap(lambda g: jnp.sqrt(sum(
            jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree_util.tree_leaves(g))))(grads)
        scales = (1.0 / jnp.maximum(1.0, norms / clip_norm)).astype(jnp.float32)
        acc = jax.tree_util.tree_map(
            lambda a, g: a + jnp.einsum(
                "b...,b->...", g.astype(jnp.float32), scales), acc, grads)
        return (acc, loss_sum + jnp.sum(losses), norm_sum + jnp.sum(norms)), None

    zero = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    (acc, loss_sum, norm_sum), _ = jax.lax.scan(
        body, (zero, jnp.zeros(()), jnp.zeros(())), jnp.arange(n_chunks))
    noisy = add_gaussian_noise(acc, key, noise_multiplier * clip_norm)
    grad = jax.tree_util.tree_map(lambda x: x / B, noisy)
    return grad, {"loss": loss_sum / B, "mean_grad_norm": norm_sum / B}


def dp_gradient_poisson(
    loss_fn: Callable[[Params, Any], jnp.ndarray],
    params: Params,
    batch: Any,          # padded batch (leaves [max_B, ...])
    mask: jnp.ndarray,   # [max_B] 1.0 = real example, 0.0 = padding
    key,
    *,
    clip_norm: float,
    noise_multiplier: float,
    expected_batch: float,
) -> Tuple[Params, dict]:
    """Eq. (7) under EXACT Poisson subsampling (Yu et al. 2019): clipped
    per-example gradients of the masked examples are summed, Gaussian noise
    N(0, sigma^2 C^2) added once, and the sum divided by the EXPECTED batch
    size qN — the estimator whose sensitivity the sampled-Gaussian RDP
    accountant analyzes. Padding slots contribute exactly zero."""
    grad_fn = jax.value_and_grad(lambda p, ex: loss_fn(
        p, jax.tree_util.tree_map(lambda x: x[None], ex)))
    losses, grads = jax.vmap(grad_fn, in_axes=(None, 0))(params, batch)
    norms = jax.vmap(lambda g: jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree_util.tree_leaves(g))))(grads)
    scales = mask / jnp.maximum(1.0, norms / clip_norm)
    acc = jax.tree_util.tree_map(
        lambda g: jnp.einsum("b...,b->...", g.astype(jnp.float32), scales),
        grads)
    noisy = add_gaussian_noise(acc, key, noise_multiplier * clip_norm)
    grad = jax.tree_util.tree_map(lambda x: x / expected_batch, noisy)
    n_real = jnp.maximum(jnp.sum(mask), 1.0)
    return grad, {"loss": jnp.sum(losses * mask) / n_real,
                  "mean_grad_norm": jnp.sum(norms * mask) / n_real}


def non_dp_gradient(
    loss_fn: Callable[[Params, Any], jnp.ndarray],
    params: Params,
    batch: Any,
    *,
    accum: int = 1,
    has_aux: bool = False,
):
    """Plain mean gradient, optionally accumulated over ``accum`` microbatch
    slices with a scan (bounds peak logits memory for large-vocab models).

    With ``has_aux`` the loss returns ``(loss, aux)`` and the whole batch's
    ``aux`` comes back third; only an unsliced batch (``accum`` 1) has one."""
    if has_aux:
        assert accum <= 1, accum
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return g, {"loss": loss}, aux
    if accum <= 1:
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        return g, {"loss": loss}
    B = jax.tree_util.tree_leaves(batch)[0].shape[0]
    assert B % accum == 0, (B, accum)
    mb = B // accum
    grad_fn = jax.value_and_grad(loss_fn)

    def body(carry, i):
        acc, loss_sum = carry
        sl = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, i * mb, mb, 0), batch)
        loss, g = grad_fn(params, sl)
        acc = jax.tree_util.tree_map(lambda a, x: a + x.astype(jnp.float32) / accum, acc, g)
        return (acc, loss_sum + loss / accum), None

    zero = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    (g, loss), _ = jax.lax.scan(body, (zero, jnp.zeros(())), jnp.arange(accum))
    return g, {"loss": loss}
