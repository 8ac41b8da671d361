"""Model FLOPs per trained token, from a configuration file's widths,
and the table of peaks.

Training counts 6 operations per matrix weight per token (forward 2,
backward 4) over every layer's matrices and the head, and attention's
two products (scores and values) over the causal half of the keys, 3
times for forward and backward.  The embedding lookup is not a product.
Recomputed forward passes are not counted, so the count is the model's,
not the program's.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def layer_matmul_params(cfg: dict) -> int:
    d, ff, H = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"])
    mlp = 3 * d * ff
    if cfg["block"] == "gqa":
        hd, KV = cfg["head_dim"], cfg["num_key_value_heads"]
        return mlp + 2 * d * H * hd + 2 * d * KV * hd
    if cfg["block"] == "mla":
        qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
        nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])
        return (mlp + d * qr + qr * H * (nope + rope) + d * (kvr + rope)
                + kvr * H * (nope + vd) + H * vd * d)
    raise ValueError(f"unknown block kind {cfg['block']!r}")


def attention_fwd_flops(cfg: dict, seq: int) -> float:
    """Forward attention products per token, causal: each query sees
    ``seq / 2`` keys on average."""
    H = cfg["num_attention_heads"]
    if cfg["block"] == "gqa":
        dq = dv = cfg["head_dim"]
    else:
        dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        dv = cfg["v_head_dim"]
    return 2 * (seq / 2) * H * (dq + dv)


def train_flops_per_token(cfg: dict, n_layers: int, seq: int) -> float:
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return (6 * (n_layers * layer_matmul_params(cfg) + head)
            + 3 * n_layers * attention_fwd_flops(cfg, seq))


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]
