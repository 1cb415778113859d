"""Loss-function unit + property tests. The TP-friendly CE rewrite must be
numerically identical to the naive take_along_axis formulation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, st

from repro.nn.losses import (accuracy, cross_entropy, dml_loss, kl_divergence,
                             macro_accuracy)


def _naive_ce(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.mean(nll)


@given(st.integers(0, 2**31 - 1), st.integers(2, 6), st.integers(2, 33))
def test_ce_matches_naive(seed, b, v):
    k = jax.random.PRNGKey(seed)
    logits = 4.0 * jax.random.normal(k, (b, 5, v))
    labels = jax.random.randint(jax.random.fold_in(k, 1), (b, 5), 0, v)
    np.testing.assert_allclose(float(cross_entropy(logits, labels)),
                               float(_naive_ce(logits, labels)),
                               rtol=1e-5, atol=1e-6)


@given(st.integers(0, 2**31 - 1))
def test_kl_nonnegative_and_zero_at_self(seed):
    k = jax.random.PRNGKey(seed)
    p = jax.random.normal(k, (3, 4, 11))
    q = jax.random.normal(jax.random.fold_in(k, 1), (3, 4, 11))
    assert float(kl_divergence(p, q)) >= -1e-6
    assert abs(float(kl_divergence(p, p))) < 1e-6


def test_kl_asymmetric():
    k = jax.random.PRNGKey(0)
    p = jax.random.normal(k, (2, 3, 9))
    q = 3.0 * jax.random.normal(jax.random.fold_in(k, 1), (2, 3, 9))
    assert not np.isclose(float(kl_divergence(p, q)), float(kl_divergence(q, p)))


def test_dml_loss_interpolates():
    k = jax.random.PRNGKey(0)
    own = jax.random.normal(k, (4, 8, 13))
    peer = jax.random.normal(jax.random.fold_in(k, 1), (4, 8, 13))
    labels = jax.random.randint(jax.random.fold_in(k, 2), (4, 8), 0, 13)
    ce = float(cross_entropy(own, labels))
    kl = float(kl_divergence(own, peer))
    for a in (0.0, 0.3, 1.0):
        expect = (1 - a) * ce + a * kl
        got = float(dml_loss(own, peer, labels, a))
        np.testing.assert_allclose(got, expect, rtol=1e-5)


def test_dml_no_gradient_through_peer():
    k = jax.random.PRNGKey(0)
    own = jax.random.normal(k, (2, 4, 7))
    labels = jnp.zeros((2, 4), jnp.int32)

    def f(peer):
        return dml_loss(own, peer, labels, 0.5)

    g = jax.grad(f)(jax.random.normal(jax.random.fold_in(k, 1), (2, 4, 7)))
    assert float(jnp.abs(g).max()) == 0.0


def test_ce_masked():
    k = jax.random.PRNGKey(0)
    logits = jax.random.normal(k, (2, 6, 5))
    labels = jax.random.randint(jax.random.fold_in(k, 1), (2, 6), 0, 5)
    mask = jnp.zeros((2, 6)).at[:, :3].set(1.0)
    full = cross_entropy(logits[:, :3], labels[:, :3])
    masked = cross_entropy(logits, labels, mask)
    np.testing.assert_allclose(float(full), float(masked), rtol=1e-5)


def test_macro_accuracy_balanced_vs_skewed():
    # a constant predictor gets high accuracy on skewed labels but low
    # macro-accuracy
    labels = jnp.asarray([0] * 9 + [1])
    logits = jnp.tile(jnp.asarray([[5.0, 0.0]]), (10, 1))
    assert abs(float(accuracy(logits, labels)) - 0.9) < 1e-6
    assert abs(float(macro_accuracy(logits, labels, 2)) - 0.5) < 1e-6


def _ref_dml(own, peer, labels, alpha, mask):
    """Float64 DML loss and KL term on ``jax.nn.log_softmax`` and
    ``take_along_axis``."""
    lp = jax.nn.log_softmax(own, axis=-1)
    lq = jax.nn.log_softmax(peer, axis=-1)
    nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    kl = jnp.sum(jnp.exp(lp) * (lp - lq), axis=-1)
    w = jnp.ones_like(nll) if mask is None else mask
    ce, kl = jnp.sum(nll * w) / jnp.sum(w), jnp.sum(kl * w) / jnp.sum(w)
    return (1 - alpha) * ce + alpha * kl, kl


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("scale", [1.0, 30.0, 1e3])
@pytest.mark.parametrize("seed", [0, 1])
def test_dml_and_kl_match_float64_log_softmax(seed, scale, masked):
    """The loss head's row-reduction log-softmax against a float64
    reference, in value and in the gradient wrt the own logits, at logit
    scales from flat to saturated; the peer gets no gradient. Tolerances
    are float32 rounding of logits of that scale."""
    k = jax.random.PRNGKey(seed)
    shape, alpha = (3, 5, 97), 0.4
    own = scale * jax.random.normal(k, shape)
    peer = scale * jax.random.normal(jax.random.fold_in(k, 1), shape)
    labels = jax.random.randint(jax.random.fold_in(k, 2), shape[:-1], 0,
                                shape[-1])
    mask = None
    if masked:
        mask = (jax.random.uniform(jax.random.fold_in(k, 3), shape[:-1])
                < 0.6).astype(jnp.float32).at[0, 0].set(1.0)

    got = [dml_loss(own, peer, labels, alpha, mask),
           kl_divergence(own, peer, mask),
           *jax.grad(dml_loss, argnums=(0, 1))(own, peer, labels, alpha, mask),
           jax.grad(kl_divergence)(own, peer, mask)]
    with jax.enable_x64(True):
        o, p = own.astype(jnp.float64), peer.astype(jnp.float64)
        m = None if mask is None else mask.astype(jnp.float64)
        ref = [*_ref_dml(o, p, labels, alpha, m),
               jax.grad(lambda x: _ref_dml(x, p, labels, alpha, m)[0])(o),
               jax.grad(lambda x: _ref_dml(x, p, labels, alpha, m)[1])(o)]
        ref = [np.asarray(x) for x in ref]

    eps = float(jnp.finfo(jnp.float32).eps)
    for g, r in zip(got[:2], ref[:2]):
        np.testing.assert_allclose(float(g), r, rtol=8 * eps,
                                   atol=8 * eps * scale)
    for g, r in zip((got[2], got[4]), ref[2:]):
        np.testing.assert_allclose(np.asarray(g), r, rtol=1e-4,
                                   atol=8 * eps * scale)
    assert float(jnp.abs(got[3]).max()) == 0.0
