"""The comparison that decides ``correct``: its three numbers on
hand-made readings."""
from __future__ import annotations

import math

import pytest

from bench import compare


def _readings(losses, momentum, change):
    return {"losses": losses, "momentum": momentum, "change": change}


REF = _readings([None, 10.0, 8.0],
                {"a": 1.0, "b": 2.0, "c": 4.0, "bias": 1e-6},
                {"a": 0.1, "b": 0.2, "c": 0.4, "bias": 0.3})


def test_numbers_are_worst_leaf_gaps_over_the_larger_norm():
    prog = _readings([None, 10.1, 8.0],
                     {"a": 1.1, "b": 2.0, "c": 4.4, "bias": 1e-6},
                     {"a": 0.1, "b": 0.2, "c": 0.36, "bias": 0.9})
    n = compare.numbers(prog, REF)
    assert n["loss_gap"] == pytest.approx(0.01)
    # leaf a: 0.1 over the median leaf's 1.5 (its own norm is smaller)
    assert n["grad_norm_gap"] == pytest.approx(0.1)
    # "bias" has a reference gradient under a thousandth of the median
    # leaf's, so its change is round-off and left out
    assert n["update_norm_gap"] == pytest.approx(0.1)
    assert compare.verdict(n, {"loss_gap": 0.02, "grad_norm_gap": 0.2,
                               "update_norm_gap": 0.2})
    assert not compare.verdict(n, {"loss_gap": 0.02, "grad_norm_gap": 0.05,
                                   "update_norm_gap": 0.2})


@pytest.mark.parametrize("loss", [None, math.nan, math.inf])
def test_a_missing_or_infinite_loss_is_not_correct(loss):
    prog = dict(REF, losses=[None, loss, 8.0])
    n = compare.numbers(prog, REF)
    assert n["loss_gap"] == math.inf
    assert not compare.verdict(n, {k: 1.0 for k in compare.NAMES})


def test_a_limit_left_unset_is_not_correct():
    n = compare.numbers(REF, REF)
    assert all(v == 0 for v in n.values())
    assert not compare.verdict(n, {"loss_gap": 0, "grad_norm_gap": 0})


def test_readings_of_different_leaves_are_an_error():
    prog = dict(REF, momentum={"a": 1.0})
    with pytest.raises(ValueError, match="leaves differ"):
        compare.numbers(prog, REF)
