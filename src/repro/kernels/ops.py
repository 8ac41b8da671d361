"""Public jit'd wrappers for the Pallas kernels.

These are the TPU runtime entry points; on this CPU container they are
exercised with ``interpret=True`` against the ``ref.py`` oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import mamba2_scan as m2
from repro.kernels import rwkv6_scan as r6
from repro.kernels import fused_update as fu


# ---------------------------------------------------------------------------
# flash attention with GQA folding + custom VJP


def _fold_gqa(q, KV):
    """[b, sq, H, d] -> [b, KV, G*sq, d] (group heads along seq)."""
    b, sq, H, d = q.shape
    G = H // KV
    q = q.reshape(b, sq, KV, G, d)
    q = jnp.moveaxis(q, 1, 3)                 # [b, KV, G, sq, d]
    return q.reshape(b, KV, G * sq, d)


def _unfold_gqa(o, H, sq):
    b, KV, gs, d = o.shape
    G = H // KV
    o = o.reshape(b, KV, G, sq, d)
    o = jnp.moveaxis(o, 3, 1)                 # [b, sq, KV, G, d]
    return o.reshape(b, sq, H, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: [b, sq, H, d]; k, v: [b, sk, KV, d] (H % KV == 0).
    Returns o: [b, sq, H, d]."""
    o, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return o


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    b, sq, H, d = q.shape
    KV = k.shape[2]
    qf = _fold_gqa(q, KV)
    kf = jnp.swapaxes(k, 1, 2)                # [b, KV, sk, d]
    vf = jnp.swapaxes(v, 1, 2)
    o, lse = fa.flash_fwd(qf, kf, vf, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    return _unfold_gqa(o, H, sq), (qf, kf, vf, o, lse)


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    o, res = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return o, res


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, do):
    qf, kf, vf, of, lse = res
    b, KV, gs, d = qf.shape
    H = do.shape[2]
    sq = do.shape[1]
    dof = _fold_gqa(do, KV)
    dq, dk, dv = fa.flash_bwd(qf, kf, vf, of, lse, dof, causal=causal,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return (_unfold_gqa(dq, H, sq),
            jnp.swapaxes(dk, 1, 2), jnp.swapaxes(dv, 1, 2))


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# recurrences (inference/prefill path; training uses the jnp scan refs)


def rwkv6_scan(r, k, v, w, u, S0, *, chunk: int = 32,
               interpret: bool = False):
    """Layout [b, s, h, hd] (model-side) -> kernel layout [b, h, s, hd]."""
    tr = lambda t: jnp.swapaxes(t, 1, 2)
    y, sT = r6.rwkv6_scan(tr(r), tr(k), tr(v), tr(w), u, S0,
                          chunk=chunk, interpret=interpret)
    return tr(y), sT


def mamba2_scan(x, dt, decay, B, C, S0, *, chunk: int = 32,
                interpret: bool = False):
    """Model-side layouts: x [b,s,h,p]; dt/decay [b,s,h]; B,C [b,s,g,n]
    (groups broadcast to heads here)."""
    h = x.shape[2]
    g = B.shape[2]
    rep = h // g
    tr = lambda t: jnp.swapaxes(t, 1, 2)
    Bh = tr(jnp.repeat(B, rep, axis=2))
    Ch = tr(jnp.repeat(C, rep, axis=2))
    y, sT = m2.mamba2_scan(tr(x), jnp.moveaxis(dt, 1, 2),
                           jnp.moveaxis(decay, 1, 2), Bh, Ch, S0,
                           chunk=chunk, interpret=interpret)
    return tr(y), sT


fused_update = fu.fused_update
