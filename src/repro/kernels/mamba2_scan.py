"""Mamba-2 SSD recurrence as a chunked Pallas TPU kernel.

Scalar-per-head decay makes the chunked form a pair of masked matmuls
(the SSD "chunked dual form"): within a chunk,

    y_t = cp_t·(C_t·S_0) + Σ_{j≤t} (cp_t/cp_j)·(C_t·B_j)·(dt_j x_j)

with cp the inclusive cumulative decay product; cross-chunk state S [p, n]
is carried in VMEM scratch across the sequential chunk grid dim.

Oracle: repro.kernels.ref.mamba2_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-24


def _ssd_kernel(x_ref, dt_ref, de_ref, b_ref, c_ref, s0_ref,
                y_ref, sT_ref, s_scr, *, nc, chunk):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)        # [C, p]
    dt = dt_ref[0, 0].astype(jnp.float32)      # [C, 1]
    de = de_ref[0, 0].astype(jnp.float32)      # [C, 1] decay in (0,1]
    B = b_ref[0, 0].astype(jnp.float32)        # [C, n]
    C = c_ref[0, 0].astype(jnp.float32)        # [C, n]
    S = s_scr[...]                             # [p, n]

    cp = jnp.cumprod(de, axis=0)               # inclusive [C, 1]
    dtx = dt * x                               # [C, p]

    score = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    ratio = cp / jnp.maximum(cp.T, _EPS)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    score = jnp.where(rows >= cols, score * ratio, 0.0)

    y_intra = jax.lax.dot_general(score, dtx, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_state = cp * jax.lax.dot_general(
        C, S, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y_intra + y_state).astype(y_ref.dtype)

    cp_last = cp[-1:]                          # [1, 1]
    tail = (cp_last / jnp.maximum(cp, _EPS)) * dtx   # [C, p]
    S_new = cp_last * S + jax.lax.dot_general(
        tail, B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scr[...] = S_new

    @pl.when(ic == nc - 1)
    def _done():
        sT_ref[0, 0] = S_new


def mamba2_scan(x, dt, decay, B, C, S0, *, chunk: int = 32,
                interpret: bool = False):
    """x: [b,h,s,p]; dt,decay: [b,h,s]; B,C: [b,h,s,n]; S0: [b,h,p,n] fp32.
    Returns (y [b,h,s,p], S_T fp32).

    ``dt``/``decay`` enter the kernel as [b,h,s,1] so their (chunk, 1)
    blocks meet the TPU compiler's last-two-dims rule."""
    b, h, s, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    kernel = functools.partial(_ssd_kernel, nc=nc, chunk=chunk)
    y, sT = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt[..., None], decay[..., None], B, C, S0)
    return y, sT
