"""Plain float32 llama-style block: RMSNorm, grouped-query attention
with RoPE, SwiGLU MLP, pre-norm residuals (the block of granite-8b)."""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference import common as c


def layer_shapes(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, KV = cfg["head_dim"], cfg["num_key_value_heads"]
    return {**c.swiglu_shapes(cfg),
            ("attn", "wq"): (d, H * hd), ("attn", "wk"): (d, KV * hd),
            ("attn", "wv"): (d, KV * hd), ("attn", "wo"): (H * hd, d)}


def layer(cfg: dict, p: dict, x, lowp: bool = False):
    b, s, _ = x.shape
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = p["attn"]
    h = c.rmsnorm(x, p["ln1"]["scale"], eps)
    q = c.rope(c.dot(h, a["wq"], lowp).reshape(b, s, H, hd), theta)
    k = c.rope(c.dot(h, a["wk"], lowp).reshape(b, s, KV, hd), theta)
    v = c.dot(h, a["wv"], lowp).reshape(b, s, KV, hd)
    # query head i reads key/value head i // (H / KV)
    k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
    o = c.causal_attention(q, k, v, lowp)
    x = x + c.dot(o.reshape(b, s, H * hd), a["wo"], lowp)
    return x + c.swiglu(p["mlp"], c.rmsnorm(x, p["ln2"]["scale"], eps), lowp)


def layer_matmul_params(cfg: dict) -> int:
    d, ff, H = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"])
    hd, KV = cfg["head_dim"], cfg["num_key_value_heads"]
    return 3 * d * ff + 2 * d * H * hd + 2 * d * KV * hd


def attention_fwd_flops(cfg: dict, seq: int) -> float:
    """Causal: each query sees ``seq / 2`` keys on average."""
    hd = cfg["head_dim"]
    return 2 * (seq / 2) * cfg["num_attention_heads"] * (hd + hd)


def smoke(cfg: dict, s) -> dict:
    if s.mla is not None:
        raise ValueError(f"{s.name} has latent attention, not this block")
    return {"head_dim": s.hd}
