"""Flash attention as a Pallas TPU kernel (fwd + bwd), VMEM-tiled.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * tiles are MXU-aligned (block_q x d and block_k x d with d padded to the
    128-lane register shape by the caller/ops.py);
  * the softmax running max / denominator / output accumulator live in VMEM
    scratch across the sequential `k` grid dimension
    (dimension_semantics: the last grid dim is "arbitrary" = sequential,
    everything else parallel);
  * GQA is folded to MHA by stacking the G query heads of a group along the
    sequence axis (positions recovered with mod-sq arithmetic), so the k/v
    blocks for a group are fetched once — the TPU analogue of shared-memory
    reuse across warps.

Oracle: repro.kernels.ref.attention_ref.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                scale, causal, block_q, block_k, sq, sk, nk):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)           # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)           # [bk, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < sk
    if causal:
        qpos = jnp.remainder(rows, sq)            # GQA group-folding
        mask = mask & (qpos >= kpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                           # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_scr[...] = corr * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        lsum = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / lsum).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(lsum)


def flash_fwd(q, k, v, *, causal: bool = True, block_q: int = 128,
              block_k: int = 128, interpret: bool = False
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q: [b, h, sq_folded, d] (GQA pre-folded); k, v: [b, h, sk, d].

    Returns (o, lse) with lse [b, h, sq_folded, 1]: the trailing unit
    axis keeps its block's last two dims legal for the TPU compiler
    ((block_q, 1): divisible by 8 / equal to the array's dim).
    ``sq_folded = G * sq`` when folding; causal masking recovers
    positions as ``row % sq`` with sq == sk."""
    b, h, sqf, d = q.shape
    sk = k.shape[2]
    sq = sk if causal else sqf
    nq = (sqf + block_q - 1) // block_q
    nk = (sk + block_k - 1) // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, sq=sq, sk=sk, nk=nk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sqf, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sqf, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward: dq


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
               acc_scr, *, scale, causal, block_q, block_k, sq, sk, nk):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)
    dl = dl_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < sk
    if causal:
        mask = mask & (jnp.remainder(rows, sq) >= kpos)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dl) * scale
    acc_scr[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dk / dv


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale, causal, block_q, block_k, sq, sk, nq):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)
    dl = dl_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < sk
    if causal:
        mask = mask & (jnp.remainder(rows, sq) >= kpos)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dv_scr[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dl) * scale
    dk_scr[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_bwd(q, k, v, o, lse, do, *, causal: bool = True,
              block_q: int = 128, block_k: int = 128,
              interpret: bool = False):
    b, h, sqf, d = q.shape
    sk = k.shape[2]
    sq = sk if causal else sqf
    nq = (sqf + block_q - 1) // block_q
    nk = (sk + block_k - 1) // block_k
    scale = 1.0 / math.sqrt(d)
    dl = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
                 keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, sq=sq, sk=sk,
                          nk=nk),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, dl)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, sq=sq, sk=sk,
                          nq=nq),
        grid=(b, h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, ik, iq: (b, h, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, dl)
    return dq, dk, dv
