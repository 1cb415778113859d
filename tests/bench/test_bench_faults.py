"""A run whose timed path is broken underneath comes out not correct: each
fault a training cell can have on one chip (``bench/faults.py``), planted in
the program at a size a test run holds."""
import pytest

import benchtiny
from bench import faults, family_lm


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("bench_faults"))


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(root, monkeypatch, fault):
    build = family_lm.build
    monkeypatch.setattr(family_lm, "build",
                        lambda *a: faults.plant(build(*a), fault))
    out = benchtiny.run(*root, "tiny.lm")
    assert out["correct"] is False, out["checks"]
