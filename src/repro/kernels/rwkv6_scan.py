"""RWKV-6 WKV recurrence as a chunked Pallas TPU kernel.

The per-channel data-dependent decay recurrence

    y_t = r_t · (S_{t−1} + diag(u)·k_t v_tᵀ)
    S_t = diag(w_t)·S_{t−1} + k_t v_tᵀ

is evaluated chunk-parallel: within a chunk of C steps the pairwise term
becomes a masked [C, C] matmul after rescaling r/k by the running decay
product (r' = r⊙cw, k' = k/cp), and the cross-chunk state is carried in
VMEM scratch across the sequential chunk grid dimension — the TPU analogue
of the CUDA kernels' per-SM running state, restructured for the MXU.

Numerics: the decay products are fp32 and clamped; valid for w ∈ [~0.5, 1)
over chunk lengths ≤ 64 (the regime RWKV-6 trains in; the trained w0/lora
parameterization keeps w ≈ exp(−exp(·)) ∈ (0.6, 0.999)).

Oracle: repro.kernels.ref.rwkv6_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-24


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                y_ref, sT_ref, s_scr, *, nc, chunk):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)        # [C, hd]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)           # [1, hd]
    S = s_scr[...]                             # [hd_k, hd_v]

    cp = jnp.cumprod(w, axis=0)                # inclusive products
    cw = cp / w                                # exclusive (w>0 elementwise)

    r_s = r * cw                               # decay-weighted receptance
    k_s = k / jnp.maximum(cp, _EPS)
    score = jax.lax.dot_general(r_s, k_s, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    score = jnp.where(rows > cols, score, 0.0)
    diag = jnp.sum(r * u * k, axis=1)                    # bonus term
    score = score + jnp.where(rows == cols, diag[:, None], 0.0)

    y_intra = jax.lax.dot_general(score, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_state = jax.lax.dot_general(r_s, S, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y_intra + y_state).astype(y_ref.dtype)

    cp_last = cp[-1]                                      # [hd]
    k_tail = k * (cp_last[None, :] / jnp.maximum(cp, _EPS))
    S_new = cp_last[:, None] * S + jax.lax.dot_general(
        k_tail, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scr[...] = S_new

    @pl.when(ic == nc - 1)
    def _done():
        sT_ref[0, 0] = S_new


def rwkv6_scan(r, k, v, w, u, S0, *, chunk: int = 32,
               interpret: bool = False):
    """r,k,v,w: [b, h, s, hd]; u: [h, hd]; S0: [b, h, hd, hd] fp32.
    Returns (y [b,h,s,hd] fp32-accurate in r.dtype, S_T fp32).

    ``u`` enters the kernel as [h, 1, hd] so its (1, hd) block spans the
    array's last two dims, as the TPU compiler requires."""
    b, h, s, hd = r.shape
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    kernel = functools.partial(_wkv_kernel, nc=nc, chunk=chunk)
    y, sT = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, h, ic: (h, 0, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, hd), r.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u.reshape(h, 1, hd), S0)
    return y, sT
