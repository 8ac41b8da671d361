"""Plain float32 pieces shared by the reference blocks (the block
files ``gqa.py``, ``mla.py``) and the reference's embedding and head.

Nothing here imports the program.  Every matrix product goes through
:func:`dot` / :func:`einsum`, which run at the precision the caller set
(``jax.default_matmul_precision("highest")`` for the reference) and, for
the control, first round both operands to float8 e4m3 with one scale per
tensor (amax to 448) and a straight-through gradient: the reference
computed one precision below the configuration's bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0


def fp8_round(x):
    """``x`` rounded to float8 e4m3 under a per-tensor scale; the
    gradient passes through unrounded."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = F8_MAX / amax
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)


def dot(a, b, lowp: bool):
    if lowp:
        a, b = fp8_round(a), fp8_round(b)
    return a @ b


def einsum(spec: str, a, b, lowp: bool):
    if lowp:
        a, b = fp8_round(a), fp8_round(b)
    return jnp.einsum(spec, a, b)


def rmsnorm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """x: [b, s, h, d]; rotate half (first half pairs with second half)
    at positions 0..s-1."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, lowp: bool, block: int = 1024):
    """softmax(q k^T / sqrt(dq)) v with a causal mask; q, k: [b, s, h,
    dq], v: [b, s, h, dv].  Queries are taken ``block`` at a time, each
    block against all the keys it may see, so no [s, s] matrix is held
    at once; every row's softmax is exact."""
    s = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    outs = []
    for lo in range(0, s, block):
        hi = min(lo + block, s)
        sc = einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi], lowp) * scale
        qpos = jnp.arange(lo, hi)[:, None]
        kpos = jnp.arange(hi)[None, :]
        sc = jnp.where(qpos >= kpos, sc, -jnp.inf)
        outs.append(einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1),
                           v[:, :hi], lowp))
    return jnp.concatenate(outs, 1)


def swiglu_shapes(cfg: dict) -> dict:
    """The leaves of a pre-norm SwiGLU layer outside its attention:
    both norm scales and the MLP."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return {("ln1", "scale"): (d,), ("ln2", "scale"): (d,),
            ("mlp", "wg"): (d, ff), ("mlp", "w1"): (d, ff),
            ("mlp", "w2"): (ff, d)}


def swiglu(p, h, lowp: bool):
    return dot(jax.nn.silu(dot(h, p["wg"], lowp)) * dot(h, p["w1"], lowp),
               p["w2"], lowp)


def embed(tok, tokens, scale: float):
    return jnp.take(tok, tokens, 0) * scale


def head_loss(x, ln_f, w_out, targets, eps: float, scale: float,
              lowp: bool):
    """Mean token cross-entropy of ``(rmsnorm(x) * scale) @ w_out``
    over every row of ``w_out``'s vocabulary."""
    logits = dot(rmsnorm(x, ln_f, eps) * scale, w_out, lowp)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)
