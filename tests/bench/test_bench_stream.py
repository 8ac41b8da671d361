"""A whole run of ``granite8b-stream-1chip`` past its look for a chip,
at smoke widths on the CPU: sound, it is correct; with its timed path
broken underneath, or with the control in the program's place, it is
not."""
from __future__ import annotations

import pytest

import benchsmoke

CELL = "granite8b-stream-1chip"
JOB = dict(batch=4, seq=32)


def test_sound_run_is_correct():
    out = benchsmoke.run(CELL, **JOB)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(fault):
    out = benchsmoke.run(CELL, fault=fault, **JOB)
    assert not out["correct"], out["checks"]


def test_control_fails_a_limit():
    from bench import compare
    nums, limits = benchsmoke.control(CELL, **JOB)
    assert not compare.verdict(nums, limits), (nums, limits)


def test_tick_without_prediction_fails_a_limit():
    """The stream tick with its weight prediction left out (``s = 0`` on
    every stage), in the program's place."""
    from bench import compare
    nums, limits = benchsmoke.control(CELL, fault="no_prediction", **JOB)
    assert not compare.verdict(nums, limits), (nums, limits)
