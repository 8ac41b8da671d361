"""The benchmark's plain float32 reference against the repo's model, at
smoke widths on the CPU.

Each block kind (``bench/reference/gqa.py``, ``mla.py``, found through
``bench.cells.block`` by the configuration's ``block`` key), the
embedding and the head with its loss, at the block's scales, are run on
the same weights as the model's own ``block_apply`` / ``embed`` /
``head_loss`` in float32 at ``highest`` matmul precision.  Tolerance: 1e-5 of the output's scale,
about a hundred float32 roundings over sums of at most a few hundred
terms; a wrong equation (a missing norm, a RoPE on the wrong half, an
unscaled embedding) moves the output by order one.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchsmoke import smoke_cfg

ROOT = Path(__file__).resolve().parents[2]
ARCHES = ("granite-8b", "minicpm3-4b")
TOL = 1e-5


def _setup(arch):
    from repro.configs import get_config, smoke_config
    from bench import weights as wt
    cfg = smoke_cfg(json.loads(
        (ROOT / f"bench/configs/{arch}.json").read_text()))
    repo = smoke_config(get_config(arch)).replace(
        param_dtype="float32", compute_dtype="float32")
    w = wt.draw_all(cfg, 2, wt.weights_key(11))
    return cfg, repo, w


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.max(np.abs(want)))
    assert scale > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL * scale)


@pytest.mark.parametrize("arch", ARCHES)
def test_block_matches_model(arch):
    from repro.models.transformer import block_apply
    from bench import cells
    cfg, repo, w = _setup(arch)
    layer = cells.block(ROOT, cfg["block"]).layer
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (2, 48, cfg["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        for i in range(2):
            p = jax.tree.map(lambda a: a[i], w["layers"])
            want = block_apply(repo, p, x)[0]
            _close(layer(cfg, p, x), want)


@pytest.mark.parametrize("arch", ARCHES)
def test_embedding_and_head_match_model(arch):
    from repro.models import Model
    from bench import cells
    from bench.reference import common as c
    cfg, repo, w = _setup(arch)
    blk = cells.block(ROOT, cfg["block"])
    model = Model(repo)
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 24), 0,
                              cfg["vocab_size"])
    tgts = jnp.roll(toks, -1, 1)
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, 24, cfg["hidden_size"]))
    out = w["outer"]
    w_out = out["embed"]["tok"].T if cfg["tie_word_embeddings"] \
        else out["embed"]["unembed"]
    with jax.default_matmul_precision("highest"):
        _close(c.embed(out["embed"]["tok"], toks, blk.embed_scale(cfg)),
               model.embed(out, {"tokens": toks}))
        _close(c.head_loss(x, out["ln_f"]["scale"], w_out, tgts,
                           cfg["rms_norm_eps"], blk.logit_scale(cfg), False),
               model.head_loss(out, x, tgts))


def test_control_rounds_to_float8():
    """The control's operands carry float8 e4m3's 3 mantissa bits under
    a per-tensor scale, and its gradient passes through."""
    from bench.reference import common as c
    x = jax.random.normal(jax.random.PRNGKey(0), (256,))
    q = c.fp8_round(x)
    rel = jnp.abs(q - x) / jnp.abs(x)
    assert float(jnp.max(jnp.where(jnp.abs(x) > 0.05, rel, 0))) <= 2 ** -4
    assert float(jnp.mean(rel)) > 2 ** -9     # far coarser than bf16
    g = jax.grad(lambda v: jnp.sum(c.fp8_round(v) * 3.0))(x)
    np.testing.assert_array_equal(np.asarray(g), 3.0)
