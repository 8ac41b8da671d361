"""Finding a cell's pieces by the names in ``BENCHMARK.json``.

Every piece is a file of its own under the checkout's ``bench/``:
the configuration at the path its ``configs`` entry names, its
architecture in the block file ``bench/reference/<block>.py`` that the
configuration's ``block`` key names, the traffic (the training job) at
``bench/traffic/<traffic>.json``, the limits of the comparison at
``bench/workloads/<cell>.json``, and each per-layer metric's reader at
``bench/metrics/<metric>.py``.  Adding a cell, a configuration, a block
kind or a metric adds files and entries; no code changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import math
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict

ROOT = Path(__file__).resolve().parents[1]


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def load(root: Path, workload: str) -> dict:
    """The cell ``workload``: its entry, configuration, job, limits and
    the metrics it reports (end-to-end, per-layer)."""
    root = Path(root)
    bm = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bm["configs"]}
    cfg = _json(root / configs[cell["config"]]["file"])
    job = _json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    limits_file = root / "bench" / "workloads" / f"{workload}.json"
    limits = _json(limits_file)["limits"] if limits_file.exists() else {}

    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bm["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if applies(m) and ("workloads" in m or m["moves"] in names)]
    return {"root": root, "cell": cell, "cfg": cfg, "job": job,
            "limits": limits, "end_to_end": e2e, "per_layer": per_layer}


@functools.lru_cache(maxsize=None)
def _module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(root: Path, metric: str) -> Callable[[Dict], object]:
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    return _module(path, f"bench_metric_{metric}").read


# what a block file that defines no scale of its own is given
_SCALES = {"embed_scale": lambda cfg: math.sqrt(cfg["hidden_size"]),
           "logit_scale": lambda cfg: 1.0}


def block(root: Path, name: str) -> ModuleType:
    """The block file ``bench/reference/<name>.py``, all the benchmark
    knows of one layer kind: ``layer_shapes(cfg)``, ``layer(cfg, p, x,
    lowp)``, ``layer_matmul_params(cfg)``, ``attention_fwd_flops(cfg,
    seq)``, ``smoke(cfg, s)`` (its own keys read from the repo's
    ``ArchConfig`` ``s``), and ``embed_scale(cfg)`` and
    ``logit_scale(cfg)``, which multiply the embedding and the head's
    input (``sqrt(hidden_size)`` and 1 where the file defines none)."""
    path = Path(root) / "bench" / "reference" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no block file {path} for block {name!r}")
    mod = _module(path, f"bench_block_{name}")
    for attr, default in _SCALES.items():
        if not hasattr(mod, attr):
            setattr(mod, attr, default)
    return mod
