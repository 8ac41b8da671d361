"""The block files: one per layer kind (``bench/reference/<block>.py``),
found by the configuration's ``block`` key through
``bench.cells.block``, and the only place that defines an architecture
for the benchmark.

The pins were read on the commit before the per-block switches moved
into these files, so that the move moved nothing the benchmark reads:
the FLOP count to the last digit, the leaf table (paths, shapes and
order, so every leaf's ``fold_in`` index), the weights drawn at smoke
width, and the reference's losses over ``check_steps``.  A new kind,
with its own embedding and logit scales and a residual multiplier,
needs only a new file.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import benchsmoke

ROOT = Path(__file__).resolve().parents[2]


def _cfg(name):
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


@pytest.mark.parametrize("config,layers,seq,want", [
    ("granite-8b", 2, 1024, 3_875_536_896),      # stream cell
    ("minicpm3-4b", 8, 4096, 4_765_409_280),     # seq-4k job
])
def test_flops_per_token_is_pinned(config, layers, seq, want):
    from bench import flops
    assert flops.train_flops_per_token(_cfg(config), layers, seq) == want


TABLES = {
    ("granite-8b", 2): [
        ("layers", "attn/wk", (2, 4096, 1024)),
        ("layers", "attn/wo", (2, 4096, 4096)),
        ("layers", "attn/wq", (2, 4096, 4096)),
        ("layers", "attn/wv", (2, 4096, 1024)),
        ("layers", "ln1/scale", (2, 4096)),
        ("layers", "ln2/scale", (2, 4096)),
        ("layers", "mlp/w1", (2, 4096, 14336)),
        ("layers", "mlp/w2", (2, 14336, 4096)),
        ("layers", "mlp/wg", (2, 4096, 14336)),
        ("outer", "embed/tok", (49152, 4096)),
        ("outer", "embed/unembed", (4096, 49152)),
        ("outer", "ln_f/scale", (4096,)),
    ],
    ("minicpm3-4b", 8): [
        ("layers", "attn/kv_norm/scale", (8, 256)),
        ("layers", "attn/q_norm/scale", (8, 768)),
        ("layers", "attn/w_dkv", (8, 2560, 288)),
        ("layers", "attn/w_dq", (8, 2560, 768)),
        ("layers", "attn/w_ukv", (8, 256, 5120)),
        ("layers", "attn/w_uq", (8, 768, 3840)),
        ("layers", "attn/wo", (8, 2560, 2560)),
        ("layers", "ln1/scale", (8, 2560)),
        ("layers", "ln2/scale", (8, 2560)),
        ("layers", "mlp/w1", (8, 2560, 6400)),
        ("layers", "mlp/w2", (8, 6400, 2560)),
        ("layers", "mlp/wg", (8, 2560, 6400)),
        ("outer", "embed/tok", (73728, 2560)),
        ("outer", "ln_f/scale", (2560,)),
    ],
}


@pytest.mark.parametrize("config,layers", sorted(TABLES))
def test_leaf_table_is_pinned(config, layers):
    from bench import weights as wt
    got = [(g, "/".join(p), tuple(s))
           for g, p, s in wt.leaf_table(_cfg(config), layers)]
    assert got == TABLES[config, layers]


def _checksum(w) -> float:
    """Every drawn value, each leaf against a fixed random vector of
    its own: a leaf drawn from another key, or two leaves swapped, move
    it by order one."""
    from bench import weights as wt
    total = 0.0
    for i, (_, a) in enumerate(sorted(wt.flat(w).items())):
        a = np.asarray(a, np.float64).ravel()
        total += float(a @ np.random.default_rng(i).standard_normal(a.size))
    return total


@pytest.mark.parametrize("config,want", [
    ("granite-8b", 14.099210824582313),
    ("minicpm3-4b", 19.21812780494439),
])
def test_smoke_weights_are_pinned(config, want):
    from bench import weights as wt
    w = wt.draw_all(benchsmoke.smoke_cfg(_cfg(config)), 2,
                    wt.weights_key(11))
    # a host whose CPU rounds a draw's last bit otherwise moves it by
    # far less than 1e-6; another draw moves it by order one
    assert _checksum(w) == pytest.approx(want, rel=1e-6)


# The CPU's matmuls sum in an order that depends on the host, a few
# float32 roundings of the loss (under 1e-6 of it); a scale or an
# equation that moved shifts the losses by 1e-3 or more.
LOSS_RTOL = 1e-5


@pytest.mark.parametrize("name,job,want", [
    ("granite8b-stream-1chip", dict(batch=4, seq=32),
     [None, 7.399156093597412, 7.359899044036865, 7.4558491706848145,
      7.274125099182129]),
    ("minicpm3-4b:1f1b-spmd-s2l4-b2x4096-m2:1", dict(batch=2, seq=64),
     [6.920011520385742, 6.960904598236084, 6.998743057250977]),
])
def test_reference_losses_are_pinned(name, job, want):
    import jax
    from bench.reference.train import Reference, check_steps, warmup
    spec = benchsmoke.smoke_spec(name, **job)
    j = spec["job"]
    got = Reference(spec["cfg"], j, 7, jax.devices()).run(
        check_steps(j), warmup(j) + 1)["losses"]
    assert [v is None for v in got] == [v is None for v in want]
    assert [v for v in got if v is not None] == pytest.approx(
        [v for v in want if v is not None], rel=LOSS_RTOL)


def test_unknown_block_names_its_file():
    from bench import cells, flops
    path = ROOT / "bench" / "reference" / "no_such_block.py"
    with pytest.raises(FileNotFoundError, match=str(path)):
        flops.train_flops_per_token(
            dict(_cfg("granite-8b"), block="no_such_block"), 2, 1024)
    with pytest.raises(FileNotFoundError, match=str(path)):
        cells.block(ROOT, "no_such_block")


# ``gqa.py`` with MiniCPM3-style muP scalars, each read from the
# configuration and, where it is absent, at the value that leaves the
# arithmetic as it was.
SCALED_BLOCK = '''
import math


def embed_scale(cfg):
    return cfg.get("scale_emb", math.sqrt(cfg["hidden_size"]))


def logit_scale(cfg):
    return cfg.get("logit_mult", 1.0)
'''

# the reference's whole path and its readings, run from a checkout
# whose ``bench/`` holds one more file: ``gqa``, the copy, and the copy
# with each of its scalars changed alone
MUP = dict(scale_emb=12.0, logit_mult=0.1, residual_mult=0.178)
DRIVER = '''
import json, sys
from pathlib import Path
import jax
from bench import cells
from bench.reference.train import Reference, check_steps, warmup
assert cells.ROOT == Path.cwd(), cells.ROOT
cfg, job, mup = (json.loads(a) for a in sys.argv[1:4])
n, g = check_steps(job), warmup(job) + 1
runs = {"gqa": cfg, "copy": dict(cfg, block="gqa_mup")}
runs.update({k: dict(cfg, block="gqa_mup", **{k: v}) for k, v in mup.items()})
print(json.dumps({k: Reference(c, job, 7, jax.devices()).run(n, g)
                  for k, c in runs.items()}))
'''


def test_block_file_brings_its_own_scales(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    src = (ROOT / "bench/reference/gqa.py").read_text()
    res = 'cfg.get("residual_mult", 1.0) * '
    for join in ("x = x + ", "return x + "):
        assert src.count(join) == 1, join
        src = src.replace(join, join + res)
    (tmp_path / "bench/reference/gqa_mup.py").write_text(src + SCALED_BLOCK)

    spec = benchsmoke.smoke_spec("granite8b-stream-1chip", batch=4, seq=32)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path), str(ROOT / "src")]))
    p = subprocess.run(
        [sys.executable, "-c", DRIVER, json.dumps(spec["cfg"]),
         json.dumps(spec["job"]), json.dumps(MUP)], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    gqa = out["gqa"]
    # at the default scalars the copy is gqa to the last bit
    assert out["copy"] == gqa
    for k in MUP:
        # each scalar reaches the first logged loss, and every leaf's
        # gradient is still read
        assert out[k]["momentum"].keys() == gqa["momentum"].keys()
        assert out[k]["losses"][1] != pytest.approx(gqa["losses"][1],
                                                    rel=1e-3), k
