"""A whole run of the four-chip MPMD job (``granite-8b`` under
``bench/traffic/1f1b-mpmd-s4l2-b8x1024-m8.json``: 1F1B, stage-local
over four chips with ``ppermute`` rings) past its look for a chip, at
smoke widths on four virtual CPU devices (one process each, since the
device count is fixed when JAX starts).  It is not a cell yet, so it
is held to the proven cell's limits: sound, it is correct; with its
timed path broken underneath, the exchange between chips included, it
is not."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest


ROOT = Path(__file__).resolve().parents[2]
JOB = "granite-8b:1f1b-mpmd-s4l2-b8x1024-m8:4"
SMOKE = dict(batch=8, seq=32)


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    """One compile cache for the module's runs, which share programs."""
    return str(tmp_path_factory.mktemp("jax_cache"))


def _run(cache, fault=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=cache,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    p = subprocess.run(
        [sys.executable, "tests/bench/benchsmoke.py", JOB,
         json.dumps(SMOKE)] + ([fault] if fault else []),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(cache):
    out = _run(cache)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault",
                         ["unchanged", "half_batch", "no_exchange"])
def test_broken_step_is_not_correct(cache, fault):
    out = _run(cache, fault)
    assert not out["correct"], out["checks"]

