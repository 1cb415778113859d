"""The comparison that decides ``correct``: per role, relative gaps of
losses, and of per-weight norms against that weight's reference norm or the
median one, with weights whose reference gradient is nought left out of the
change."""
import numpy as np
import pytest

import benchtiny  # noqa: F401  (puts the repository on the path)
from bench import harness


def _readings(loss, moment, change):
    return {"loss": np.asarray(loss, float),
            "moment": {"private": np.asarray(moment, float)},
            "change": {"private": np.asarray(change, float)}}


REF = _readings([[[2.0]]], [[1.0, 2.0, 3.0, 1e-6]],
                [[0.1, 0.2, 0.3, 0.5]])


def test_identical_readings_have_no_gap():
    assert set(harness.compare(REF, REF).values()) == {0.0}


def test_loss_gap_is_relative():
    prog = _readings([[[2.1]]], REF["moment"]["private"],
                     REF["change"]["private"])
    assert harness.compare(prog, REF)["private_loss_gap"] == pytest.approx(
        0.05)


def test_small_weights_are_measured_against_the_median():
    # the 4th weight's moment is tiny: its gap is over the median (1.5)
    prog = _readings(REF["loss"], [[1.0, 2.0, 3.0, 0.3]],
                     REF["change"]["private"])
    assert harness.compare(prog, REF)["private_moment_gap"] == pytest.approx(
        (0.3 - 1e-6) / 1.5)


def test_weights_with_no_reference_gradient_leave_the_change():
    # the 4th weight's reference moment is under 1e-3 of the median: its
    # change, however wrong, is not compared; the others' are
    prog = _readings(REF["loss"], REF["moment"]["private"],
                     [[0.1, 0.2, 0.3, 9.0]])
    assert harness.compare(prog, REF)["private_change_gap"] == 0.0
    prog["change"]["private"][0, 2] = 0.0
    assert harness.compare(prog, REF)["private_change_gap"] == pytest.approx(
        1.0)


def test_the_worst_role_is_taken():
    two = {"loss": np.asarray([[[2.0, 4.0]]]),
           "moment": {"private": np.ones((1, 2)), "proxy": np.ones((1, 2))},
           "change": {"private": np.ones((1, 2)), "proxy": np.ones((1, 2))}}
    prog = dict(two, loss=np.asarray([[[2.0, 5.0]]]))
    out = harness.compare(prog, two)
    assert out["private_loss_gap"] == 0.0
    assert out["proxy_loss_gap"] == out["loss_gap"] == pytest.approx(0.25)


def test_a_non_finite_reading_is_infinite():
    prog = _readings([[[np.nan]]], REF["moment"]["private"],
                     REF["change"]["private"])
    assert harness.compare(prog, REF)["private_loss_gap"] == float("inf")


def test_one_weight_far_off_moves_the_worst_moment_gap_not_the_median():
    prog = _readings(REF["loss"], [[1.0, 2.0, 3.9, 1e-6]],
                     REF["change"]["private"])
    out = harness.compare(prog, REF)
    assert out["private_moment_gap"] == pytest.approx(0.3)
    assert out["private_moment_median_gap"] == 0.0
    # every weight off by a tenth: gaps 0.1/1.5 (under the median), 0.1,
    # 0.1 and 0 (the tiny 4th), whose median lies between the middle two
    prog["moment"]["private"][0] = [1.1, 2.2, 3.3, 1e-6]
    assert harness.compare(prog, REF)[
        "private_moment_median_gap"] == pytest.approx((0.1 / 1.5 + 0.1) / 2)
