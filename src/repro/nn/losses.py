"""Losses: cross-entropy and the DML KL term (paper Eqs. 2-5)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean CE. logits [..., V]; labels [...] int; mask broadcastable to
    labels (1 = count). Audio models pass [..., K, V] / [..., K].

    Written as vocab-local reductions (max / sum-exp / masked-pick via an
    iota compare) rather than ``take_along_axis`` so that on a tensor-
    parallel mesh with vocab-sharded logits every term stays local and only
    [..,] -shaped partials cross the "model" axis — a gather of the full
    logits tensor otherwise dominates collective traffic."""
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    picked = jnp.sum(jnp.where(iota == labels[..., None].astype(jnp.int32), lf, 0.0),
                     axis=-1)
    nll = lse - picked
    if mask is None:
        return jnp.mean(nll)
    mask = jnp.broadcast_to(mask, nll.shape).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def kl_divergence(p_logits: jnp.ndarray, q_logits: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean KL[p || q] over positions (paper Eq. 3). Differentiable wrt both;
    callers stop-gradient the frozen side per the DML alternation."""
    lp = jax.nn.log_softmax(p_logits.astype(jnp.float32), axis=-1)
    lq = jax.nn.log_softmax(q_logits.astype(jnp.float32), axis=-1)
    kl = jnp.sum(jnp.exp(lp) * (lp - lq), axis=-1)
    if mask is None:
        return jnp.mean(kl)
    mask = jnp.broadcast_to(mask, kl.shape).astype(jnp.float32)
    return jnp.sum(kl * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@jax.named_scope("fl.loss")
def dml_loss(own_logits, peer_logits, labels, alpha: float,
             mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(1-alpha)·CE(own, y) + alpha·KL(own ‖ stop_grad(peer)) — Eq. 4/5."""
    peer = jax.lax.stop_gradient(peer_logits)
    return ((1.0 - alpha) * cross_entropy(own_logits, labels, mask)
            + alpha * kl_divergence(own_logits, peer, mask))


def accuracy(logits, labels, mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    pred = jnp.argmax(logits, axis=-1)
    ok = (pred == labels).astype(jnp.float32)
    if mask is None:
        return jnp.mean(ok)
    mask = jnp.broadcast_to(mask, ok.shape).astype(jnp.float32)
    return jnp.sum(ok * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def macro_accuracy(logits, labels, n_classes: int) -> jnp.ndarray:
    """Per-class accuracy averaged over classes (paper's macro-accuracy)."""
    pred = jnp.argmax(logits, axis=-1).reshape(-1)
    labels = labels.reshape(-1)
    accs = []
    for c in range(n_classes):
        m = (labels == c).astype(jnp.float32)
        accs.append(jnp.sum((pred == c) * m) / jnp.maximum(jnp.sum(m), 1.0))
    return jnp.mean(jnp.stack(accs))
