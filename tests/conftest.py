"""Shared fixtures. NOTE: no XLA_FLAGS here — tests must see ONE CPU device
(a test that needs more starts a child process with its own XLA_FLAGS)."""
import jax
import pytest

# `hypothesis` is optional in this container: register the profile only when
# the library exists; property-based tests skip via tests/_hypothesis_compat.
try:
    from hypothesis import HealthCheck, settings
except ModuleNotFoundError:
    pass
else:
    settings.register_profile(
        "repro", max_examples=25, deadline=None,
        suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large])
    settings.load_profile("repro")


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def tiny_lm_batch():
    k = jax.random.PRNGKey(1)
    B, S, V = 4, 16, 512
    toks = jax.random.randint(k, (B, S + 1), 0, V)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
