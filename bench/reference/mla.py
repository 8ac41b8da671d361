"""Plain float32 MiniCPM3 block: RMSNorm, multi-head latent attention
(low-rank q and kv with their own RMSNorms, RoPE on a decoupled 32-dim
part whose key is shared by all heads), SwiGLU MLP, pre-norm residuals.

Departure from the published model, as the program runs it: no muP
scaling of the residual branches (``scale_depth``) and plain RoPE
without longrope scaling."""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference import common as c


def layer_shapes(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd = cfg["v_head_dim"]
    return {**c.swiglu_shapes(cfg),
            ("attn", "w_dq"): (d, qr),
            ("attn", "q_norm", "scale"): (qr,),
            ("attn", "w_uq"): (qr, H * (nope + rope)),
            ("attn", "w_dkv"): (d, kvr + rope),
            ("attn", "kv_norm", "scale"): (kvr,),
            ("attn", "w_ukv"): (kvr, H * (nope + vd)),
            ("attn", "wo"): (H * vd, d)}


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


def layer_matmul_params(cfg: dict) -> int:
    d, ff, H = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"])
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (3 * d * ff + d * qr + qr * H * (nope + rope) + d * (kvr + rope)
            + kvr * H * (nope + vd) + H * vd * d)


def attention_fwd_flops(cfg: dict, seq: int) -> float:
    """Causal: each query sees ``seq / 2`` keys on average."""
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    return 2 * (seq / 2) * cfg["num_attention_heads"] * (dq + dv)


def smoke(cfg: dict, s) -> dict:
    return {k: getattr(s.mla, k) for k in (
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim")}
