"""Losses: cross-entropy and the DML KL term (paper Eqs. 2-5)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _log_softmax(x: jnp.ndarray) -> jnp.ndarray:
    """log-softmax over the last axis as plain row reductions: a row max,
    then x - (log-sum-exp + max) in one subtraction. Compiled for a TPU
    with its gradient, over logits that the head's matmul lays out with the
    vocabulary on sublanes, ``jax.nn.log_softmax``'s form (subtract the max,
    then the log-sum-exp) has the compiler fold the row max into a
    reduce-window as wide as the row, centred on every entry: quadratic in
    V. ``tests/test_tpu_compile.py`` holds the DML head to none."""
    m = jax.lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True))
    return x - (jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True)) + m)


def _picked(x: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """x[..., labels] by an iota compare, a vocab-local reduction."""
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.sum(jnp.where(iota == labels[..., None].astype(jnp.int32), x, 0.0),
                   axis=-1)


def _masked_mean(x: jnp.ndarray, mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    if mask is None:
        return jnp.mean(x)
    mask = jnp.broadcast_to(mask, x.shape).astype(jnp.float32)
    return jnp.sum(x * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean CE. logits [..., V]; labels [...] int; mask broadcastable to
    labels (1 = count). Audio models pass [..., K, V] / [..., K].

    Written as vocab-local reductions (max / sum-exp / masked-pick via an
    iota compare) rather than ``take_along_axis`` so that on a tensor-
    parallel mesh with vocab-sharded logits every term stays local and only
    [..,] -shaped partials cross the "model" axis — a gather of the full
    logits tensor otherwise dominates collective traffic."""
    lp = _log_softmax(logits.astype(jnp.float32))
    return _masked_mean(-_picked(lp, labels), mask)


def _kl(lp: jnp.ndarray, lq: jnp.ndarray) -> jnp.ndarray:
    """KL[p || q] per position, from log-probabilities."""
    return jnp.sum(jnp.exp(lp) * (lp - lq), axis=-1)


def kl_divergence(p_logits: jnp.ndarray, q_logits: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean KL[p || q] over positions (paper Eq. 3). Differentiable wrt both;
    callers stop-gradient the frozen side per the DML alternation."""
    return _masked_mean(_kl(_log_softmax(p_logits.astype(jnp.float32)),
                            _log_softmax(q_logits.astype(jnp.float32))), mask)


@jax.named_scope("fl.loss")
def dml_loss(own_logits, peer_logits, labels, alpha: float,
             mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(1-alpha)·CE(own, y) + alpha·KL(own ‖ stop_grad(peer)) — Eq. 4/5.
    CE and KL share one log-softmax of the own logits."""
    lp = _log_softmax(own_logits.astype(jnp.float32))
    lq = _log_softmax(jax.lax.stop_gradient(peer_logits).astype(jnp.float32))
    return ((1.0 - alpha) * _masked_mean(-_picked(lp, labels), mask)
            + alpha * _masked_mean(_kl(lp, lq), mask))


def accuracy(logits, labels, mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    pred = jnp.argmax(logits, axis=-1)
    return _masked_mean((pred == labels).astype(jnp.float32), mask)


def macro_accuracy(logits, labels, n_classes: int) -> jnp.ndarray:
    """Per-class accuracy averaged over classes (paper's macro-accuracy)."""
    pred = jnp.argmax(logits, axis=-1).reshape(-1)
    labels = labels.reshape(-1)
    accs = []
    for c in range(n_classes):
        m = (labels == c).astype(jnp.float32)
        accs.append(jnp.sum((pred == c) * m) / jnp.maximum(jnp.sum(m), 1.0))
    return jnp.mean(jnp.stack(accs))
