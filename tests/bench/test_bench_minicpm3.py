"""A whole run of the MiniCPM3 seq-4k job (``minicpm3-4b`` under
``bench/traffic/1f1b-spmd-s2l4-b2x4096-m2.json``: MLA, 1F1B on the IR
scan interpreter) past its look for a chip, at smoke widths on the CPU.
It is not a cell yet, so it is held to the proven cell's limits: sound,
it is correct; with its timed path broken underneath, it is not."""
from __future__ import annotations

import pytest

import benchsmoke

JOB = "minicpm3-4b:1f1b-spmd-s2l4-b2x4096-m2:1"
SMOKE = dict(batch=2, seq=64)


def test_sound_run_is_correct():
    out = benchsmoke.run(JOB, **SMOKE)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(fault):
    out = benchsmoke.run(JOB, fault=fault, **SMOKE)
    assert not out["correct"], out["checks"]
