"""Model FLOPs per trained token, from a configuration file's widths,
and the table of peaks.

Training counts 6 operations per matrix weight per token (forward 2,
backward 4) over every layer's matrices and the head, and attention's
two products (scores and values) over the causal half of the keys, 3
times for forward and backward; a layer's counts come from the
configuration's block file (``bench.cells.block``).  The embedding
lookup is not a product.  Recomputed forward passes are not counted, so
the count is the model's, not the program's.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench import cells

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def train_flops_per_token(cfg: dict, n_layers: int, seq: int) -> float:
    blk = cells.block(cells.ROOT, cfg["block"])
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return (6 * (n_layers * blk.layer_matmul_params(cfg) + head)
            + 3 * n_layers * blk.attention_fwd_flops(cfg, seq))


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]
