import os
import sys

# src layout import without install; the root holds chip_smoke.py
_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(1, _ROOT)


import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def optional_hypothesis():
    """(given, settings, st) — the real hypothesis API, or
    decoration-safe stubs that mark just the property tests as skipped
    when hypothesis isn't installed, leaving the rest of the module
    collectable (modules that are *all* property tests should use
    ``pytest.importorskip`` instead)."""
    try:
        from hypothesis import given, settings, strategies as st
        return given, settings, st
    except ImportError:
        def given(*a, **k):
            return lambda f: pytest.mark.skip(
                reason="hypothesis not installed")(f)

        def settings(*a, **k):
            return lambda f: f

        class _Strategies:
            def __getattr__(self, name):
                return lambda *a, **k: None

        return given, settings, _Strategies()


def tiny_cfg(name="granite-8b", *, n_layers=4, pipe=2, tensor=1, ticks=2,
             **kw):
    """Reduced fp32 config with a real pipeline split (CPU-friendly)."""
    from repro.configs import get_config, smoke_config
    from repro.configs.base import MeshPlan
    cfg = smoke_config(get_config(name))
    return cfg.replace(
        n_layers=n_layers,
        mesh_plan=MeshPlan(pipe=pipe, tensor=tensor, num_microbatches=ticks),
        param_dtype="float32", compute_dtype="float32", **kw)


def lm_batch(key, cfg, batch=4, seq=16):
    import jax.numpy as jnp
    k1, k2 = jax.random.split(key)
    b = {"tokens": jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size),
         "targets": jax.random.randint(k2, (batch, seq), 0, cfg.vocab_size)}
    if cfg.frontend == "audio":
        b["frames"] = jax.random.normal(k1, (batch, seq, cfg.d_model),
                                        jnp.float32)
    if cfg.frontend == "vision":
        p = min(cfg.frontend_patches, seq)
        b["patches"] = jax.random.normal(k1, (batch, p, cfg.d_model),
                                         jnp.float32)
    return b
