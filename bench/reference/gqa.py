"""Plain float32 llama-style block: RMSNorm, grouped-query attention
with RoPE, SwiGLU MLP, pre-norm residuals (the block of granite-8b)."""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference import common as c


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
