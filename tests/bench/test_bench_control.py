"""The control, the reference computed in bfloat16 and put in the
program's place, comes out not correct at a size a test run holds."""
import importlib

import jax.numpy as jnp
import pytest

import benchtiny
from bench import harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("bench_control"))


@pytest.mark.parametrize("cell", sorted(benchtiny.CELLS))
def test_control_is_not_correct(root, cell):
    r, spec = root
    c = harness.load_cell(spec, r, cell)
    family = importlib.import_module(f"bench.family_{c.config['family']}")
    fed = family.build(c.config, c.traffic, 11, None)
    steps = harness.CHECK_STEPS
    readings = harness.compare(fed.follow(steps, jnp.bfloat16),
                               fed.follow(steps))
    assert any(readings[k] > limit for k, limit in c.limits.items()), readings
