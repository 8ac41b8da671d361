"""The benchmark's layout: driven by data, sized from its configuration
files, and never on the CPU.

Each cell, configuration, traffic mix and per-layer metric is a file of
its own found by the name in ``BENCHMARK.json``; these tests pin that a
new one needs no code edit, that the configuration files hold the
registry's widths, that the FLOP counter gives the hand counts, and
that ``bench/run.py`` refuses to report without a TPU.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


CONFIG_FILES = sorted(p.stem for p in (ROOT / "bench/configs").glob("*.json"))


def _cfg(name):
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


# ------------------------------------------------------------- FLOPs


@pytest.mark.parametrize("config,layers,seq,gflop", [
    ("granite-8b", 2, 1024, 3.88),       # stream cell: 2 stages x 1
    ("granite-8b", 8, 1024, 11.9),       # mpmd cell: 4 stages x 2
    ("minicpm3-4b", 8, 4096, 4.77),      # seq-4k cell: 2 stages x 4
])
def test_flops_per_token_matches_hand_count(config, layers, seq, gflop):
    from bench import flops
    got = flops.train_flops_per_token(_cfg(config), layers, seq) / 1e9
    assert got == pytest.approx(gflop, rel=5e-3)


def test_flops_per_token_counts_causal_attention_half():
    from bench import flops
    cfg = _cfg("granite-8b")
    d = (flops.train_flops_per_token(cfg, 1, 2048)
         - flops.train_flops_per_token(cfg, 1, 1024))
    # 3 x (scores + values) x 2 FLOPs x 512 more keys on average x 32
    # heads x 128 dims
    assert d == 3 * 2 * 2 * 512 * 32 * 128


def test_peaks_known_device_and_unknown_is_an_error():
    from bench import flops
    assert flops.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        flops.peak("cpu")


# ------------------------------------------------------ configurations


def test_benchmark_configs_name_their_files():
    for name, c in CONFIGS.items():
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == name and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_config_file_widths_match_registry(name):
    from bench import cells
    from repro.configs import get_config
    cfg = _cfg(name)
    reg = get_config(cfg["repo_arch"])
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["vocab_size"], cfg["vocab_rows"],
            cfg["tie_word_embeddings"]) == (
        reg.d_model, reg.d_ff, reg.n_heads, reg.n_kv_heads,
        reg.vocab_size, reg.vocab_padded, reg.tie_embeddings)
    assert cfg["rope_theta"] == reg.rope_theta
    # the block's own widths, as its file reads them from the registry
    own = cells.block(ROOT, cfg["block"]).smoke(cfg, reg)
    assert own and {k: cfg[k] for k in own} == own
    # the depth a cell runs is its traffic's stages x layers_per_stage
    assert "num_hidden_layers" not in cfg
    assert f"published {reg.n_layers};" in cfg["reduced"]["num_hidden_layers"]


# ------------------------------------------------------ driven by data


def test_every_cell_has_its_files():
    from bench import cells, compare
    for w in BENCH["workloads"]:
        spec = cells.load(ROOT, w["name"])
        assert spec["cfg"]["name"] == w["config"]
        assert set(spec["limits"]) == set(compare.NAMES)
        assert {m["name"] for m in spec["end_to_end"]} >= {
            "tokens_per_s", "peak_hbm_gib", "setup_s"}
        for m in spec["per_layer"]:
            assert callable(cells.reader(ROOT, m["name"]))


def test_new_cell_config_and_metric_need_no_code_edit(tmp_path):
    """A checkout with one more configuration, traffic mix, cell and
    per-layer metric, each added as files and entries only."""
    from bench import cells
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = json.loads(json.dumps(BENCH))
    cfg = dict(_cfg("granite-8b"), name="granite-8b-copy")
    (tmp_path / "bench/configs/granite-8b-copy.json").write_text(
        json.dumps(cfg))
    bm["configs"].append(dict(CONFIGS["granite-8b"], name="granite-8b-copy",
                              file="bench/configs/granite-8b-copy.json"))
    job = json.loads(
        (ROOT / "bench/traffic/stream-s2l1-b8x1024.json").read_text())
    (tmp_path / "bench/traffic/stream-s2l1-b4x2048.json").write_text(
        json.dumps(dict(job, batch=4, seq=2048)))
    bm["workloads"].append({"name": "copy-stream-seq2k-1chip",
                            "config": "granite-8b-copy",
                            "traffic": "stream-s2l1-b4x2048", "chips": 1,
                            "why": "a cell added as data"})
    (tmp_path / "bench/workloads/copy-stream-seq2k-1chip.json").write_text(
        json.dumps({"limits": {"loss_gap": 1, "grad_norm_gap": 1,
                               "update_norm_gap": 1}}))
    (tmp_path / "bench/metrics/window_ms.py").write_text(
        "def read(ctx):\n"
        "    lo, hi = ctx['window']\n"
        "    return (hi - lo) * 1e-6\n")
    bm["per_layer"].append({"name": "window_ms", "unit": "ms",
                            "better": "lower", "source": "device_trace",
                            "layer": "device", "moves": "tokens_per_s",
                            "workloads": ["copy-stream-seq2k-1chip"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    spec = cells.load(tmp_path, "copy-stream-seq2k-1chip")
    assert spec["cfg"]["name"] == "granite-8b-copy"
    assert (spec["job"]["batch"], spec["job"]["seq"]) == (4, 2048)
    names = [m["name"] for m in spec["per_layer"]]
    assert "window_ms" in names
    read = cells.reader(tmp_path, "window_ms")
    assert read({"window": (0.0, 2.5e6)}) == 2.5
    assert "window_ms" not in [
        m["name"] for m in cells.load(tmp_path, "granite8b-stream-1chip")
        ["per_layer"]]


def test_unknown_cell_is_an_error():
    from bench import cells
    with pytest.raises(KeyError, match="no workload"):
        cells.load(ROOT, "no-such-cell")


# ------------------------------------------------------ never on a CPU


def test_import_initialises_no_backend():
    """Importing the harness loads no accelerator library: only a run
    that has checked its devices touches one."""
    code = (
        "import sys; sys.path[:0] = ['src', '.']\n"
        "import bench.harness, bench.calibrate, bench.trace, bench.flops\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('ok')\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr[-2000:]


def test_run_without_tpu_fails_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite8b-stream-1chip", "--seed", "3000000000", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=CPU_ENV, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert '"correct"' not in p.stdout


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    its paths has no system to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable] + BENCH["command"][1:] + [
            "--workload", "granite8b-stream-1chip", "--seed", "1",
            "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=CPU_ENV, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
