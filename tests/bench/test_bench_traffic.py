"""The traffic a cell feeds the system is made from ``--seed`` alone, and a
round counts as failed where some client's loss is not finite."""
import jax
import numpy as np
import pytest

import benchtiny  # noqa: F401  (puts the repository on the path)
from bench import system, traffic


def _key(seed):
    return np.asarray(jax.random.key_data(traffic.seed_key(seed)))


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 2 ** 33 + 7])
def test_seed_key_is_the_same_from_the_same_seed(seed):
    np.testing.assert_array_equal(_key(seed), _key(seed))


def test_seed_key_keeps_the_bits_above_32():
    assert not np.array_equal(_key(5), _key(2 ** 32 + 5))


def test_seed_key_refuses_a_negative_seed():
    with pytest.raises(ValueError):
        traffic.seed_key(-1)


def test_lm_federation_is_made_from_the_seed():
    def make(seed):
        data, test = traffic.lm_federation(traffic.seed_key(seed), 2, 3, 8,
                                           16, 4)
        return [np.asarray(d) for d in data] + [np.asarray(test)]

    a, b, c = make(11), make(11), make(12)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert [x.shape for x in a] == [(3, 9), (3, 9), (4, 9)]
    assert all(0 <= x.min() and x.max() < 16 for x in a)
    assert any(not np.array_equal(x, z) for x, z in zip(a, c))


def test_rounds_failed_counts_rounds_with_a_non_finite_loss():
    fed = system.Federation(0)
    fed.rounds_per_block = 3
    loss = np.ones((3, 4))
    loss[1, 2] = np.nan
    proxy = np.ones((3, 4))
    proxy[2, 0] = np.inf
    proxy[1, 0] = np.nan
    assert fed.rounds_failed({"private_loss": loss, "proxy_loss": proxy}) == 2
