"""Plain float32 MiniCPM3 block: RMSNorm, multi-head latent attention
(low-rank q and kv with their own RMSNorms, RoPE on a decoupled 32-dim
part whose key is shared by all heads), SwiGLU MLP, pre-norm residuals.

Departure from the published model, as the program runs it: no muP
scaling of the residual branches (``scale_depth``) and plain RoPE
without longrope scaling."""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference import common as c


def layer(cfg: dict, p: dict, x, lowp: bool = False):
    b, s, _ = x.shape
    H = cfg["num_attention_heads"]
    kvr = cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = p["attn"]
    h = c.rmsnorm(x, p["ln1"]["scale"], eps)
    q = c.rmsnorm(c.dot(h, a["w_dq"], lowp), a["q_norm"]["scale"], eps)
    q = c.dot(q, a["w_uq"], lowp).reshape(b, s, H, nope + rope)
    q_nope, q_rope = q[..., :nope], c.rope(q[..., nope:], theta)
    dkv = c.dot(h, a["w_dkv"], lowp)
    kv = c.rmsnorm(dkv[..., :kvr], a["kv_norm"]["scale"], eps)
    kv = c.dot(kv, a["w_ukv"], lowp).reshape(b, s, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_rope = c.rope(dkv[..., kvr:][:, :, None, :], theta)
    k_rope = jnp.broadcast_to(k_rope, (b, s, H, rope))
    o = c.causal_attention(jnp.concatenate([q_nope, q_rope], -1),
                           jnp.concatenate([k_nope, k_rope], -1), v, lowp)
    x = x + c.dot(o.reshape(b, s, H * vd), a["wo"], lowp)
    return x + c.swiglu(p["mlp"], c.rmsnorm(x, p["ln2"]["scale"], eps), lowp)
