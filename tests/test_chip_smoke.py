"""``chip_smoke.py`` on CPU: its phases at smoke widths, its refusal to
run without a TPU, and the pieces it leans on (the compile-cache helper,
the bigram size guard, the profiler's reported fallback)."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import chip_smoke
from repro.data import DataConfig, SyntheticLM
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("phase", ["A", "B"])
def test_train_phase_runs_at_smoke_size(phase, capsys):
    argv = chip_smoke.phase_argv(phase, batch=4, seq=32, steps=3,
                                 smoke=True)
    losses = chip_smoke.run_train_phase(phase, argv)
    assert len(losses) == 3
    out = capsys.readouterr().out
    assert f"# smoke phase {phase}: 3 steps" in out
    assert "profile=analytic" in out


def test_check_losses_rejects_nonfinite_and_off_band():
    ok = [7.4, 7.3]
    chip_smoke.check_losses("x", ok, first_valid=0, vocab=1024)
    with pytest.raises(AssertionError, match="non-finite"):
        chip_smoke.check_losses("x", [7.4, float("nan")], first_valid=0,
                                vocab=1024)
    with pytest.raises(AssertionError, match="not within"):
        chip_smoke.check_losses("x", [6.8], first_valid=0, vocab=1024)


_MPMD = """
import chip_smoke as cs
for dtype, rtol in (("float32", 1e-6), ("bfloat16", cs.REF_LOSS_RTOL)):
    cs.run_mpmd_phase(cs.mpmd_argv(batch=8, seq=32, smoke=True,
                                   dtype=dtype), steps=2, rtol=rtol)
"""


def test_mpmd_phase_on_four_cpu_devices():
    """The four-chip phase on four virtual CPU devices: stage weights on
    4 distinct devices, and the first loss equal to the per-device
    float32 reference to 1e-6 relative at float32 compute (and within
    the chip's tolerance at bfloat16)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", _MPMD], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert p.stdout.count("# stage weights on 4 distinct devices") == 2
    assert p.stdout.count("# first loss") == 2


_MPMD_INIT = """
import jax, numpy as np
import chip_smoke as cs
from repro.api import Runtime
from repro.launch import train
run = train.setup(train.parse_args(cs.mpmd_argv(batch=8, seq=32, smoke=True)))
rt = Runtime(run.plan, run.model, run.rc)
key = jax.random.PRNGKey(3)
got = rt.init(key, run.batch_sds)
want = jax.jit(lambda k: rt.init_state(run.model.init(k), run.batch_sds))(key)
assert jax.tree.structure(got) == jax.tree.structure(want)
for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
for leaf in jax.tree.leaves(got["params"]["stages"]):
    assert len({s.device for s in leaf.addressable_shards}) == 4
print("same bits")
"""


def test_mpmd_init_draws_stages_apart_with_the_same_bits():
    """``Runtime.init`` under mpmd draws the layers split over the pipe
    devices, and its state is bit-identical to the unsplit jitted init."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", _MPMD_INIT], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "same bits" in p.stdout


def test_main_refuses_a_host_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "needs a TPU" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "no repro package" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_the_environment(monkeypatch,
                                               cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable_compile_cache() == want


def test_bigram_stream_refuses_a_published_vocabulary():
    with pytest.raises(ValueError, match="--data uniform"):
        SyntheticLM(DataConfig(49152, 16, 2, kind="bigram"))
    data = SyntheticLM(DataConfig(49152, 16, 2, kind="uniform"))
    assert data.batch_at(0)["tokens"].max() < 49152


def test_profiler_reports_its_fallback(monkeypatch):
    from repro.configs import get_config, smoke_config
    from repro.planner import profiler

    def refuse(*a, **k):
        raise RuntimeError("no backend")

    monkeypatch.setattr(profiler, "_hlo_layer", refuse)
    cfg = smoke_config(get_config("granite-8b"))
    prof = profiler.profile_model(cfg, method="auto")
    assert prof.method == "analytic"
    assert prof.fallback == "hlo failed: RuntimeError: no backend"
    assert profiler.profile_model(cfg, method="analytic").fallback == ""
    with pytest.raises(RuntimeError):
        profiler.profile_model(cfg, method="hlo")

