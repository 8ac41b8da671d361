"""Per-layer cost profiles feeding the stage partitioner.

Three acquisition methods, best-effort in this order under ``"auto"``:

  * ``"hlo"``    — lower + compile one transformer block for the config
                   and run the trip-count-aware HLO counters of
                   ``runtime/hlo_cost.py`` over the compiled text (exact
                   FLOPs/bytes for what XLA will actually execute).
  * ``"timed"``  — execute the block and measure wall time (the
                   PipeDream approach: profile, don't model); FLOPs are
                   then back-filled analytically so the partitioner's
                   compute terms stay populated.
  * ``"analytic"`` — closed-form FLOPs from ``ArchConfig.param_count``
                   (2·params·tokens per matmul-dominated layer); always
                   available, used as the fallback of last resort.

All blocks of one config are identical, so one representative block is
profiled and replicated ``n_layers`` times; per-layer overrides (for
heterogeneous stacks, e.g. hybrid SSM+attention) can scale individual
entries via ``scale``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, replace
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class LayerProfile:
    name: str
    flops: float            # forward FLOPs for one (batch, seq) slab
    param_bytes: float
    act_bytes: float        # output activation bytes (cut cost if split here)
    time_s: float = 0.0     # measured fwd wall time (timed method only)


@dataclass(frozen=True)
class ModelProfile:
    arch: str
    method: str
    batch: int
    seq: int
    layers: Tuple[LayerProfile, ...]
    # why ``method`` is not the one asked for ("" when it is): "auto"
    # falls back from "hlo" to "analytic" and says so here
    fallback: str = ""

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    def total_flops(self) -> float:
        return sum(lp.flops for lp in self.layers)

    def scaled(self, scale: Sequence[float]) -> "ModelProfile":
        """Per-layer compute multipliers (heterogeneous-stack modelling)."""
        if len(scale) != self.n_layers:
            raise ValueError(f"{len(scale)} scales for {self.n_layers} layers")
        return replace(self, layers=tuple(
            replace(lp, flops=lp.flops * s, time_s=lp.time_s * s)
            for lp, s in zip(self.layers, scale)))


def synthetic_profile(compute: Sequence[float], *, act_bytes: float = 0.0,
                      name: str = "synthetic") -> ModelProfile:
    """Profile from raw per-layer compute costs (tests / benchmarks).

    ``act_bytes`` defaults to 0 so abstract unit-cost profiles don't get
    dominated by the bytes→seconds hardware conversion; pass real byte
    counts to make transfer terms meaningful."""
    return ModelProfile(name, "synthetic", 1, 1, tuple(
        LayerProfile(f"layer{j}", float(c), 0.0, float(act_bytes))
        for j, c in enumerate(compute)))


# ---------------------------------------------------------------------------
# analytic


def _per_layer_params(cfg) -> float:
    emb = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    body = max(0, cfg.param_count() - emb)
    return body / max(1, cfg.n_layers)


def _analytic_layer(cfg, batch: int, seq: int) -> LayerProfile:
    p = _per_layer_params(cfg)
    pdt = jnp.dtype(cfg.param_dtype).itemsize
    cdt = jnp.dtype(cfg.compute_dtype).itemsize
    tokens = batch * seq
    # matmul-dominated: 2 FLOPs per param per token, plus O(s²d) attention
    flops = 2.0 * p * tokens
    if cfg.ssm is None:
        flops += 4.0 * batch * seq * seq * cfg.n_heads * cfg.hd
    act = float(batch * seq * cfg.d_model * cdt)
    return LayerProfile("block", flops, p * pdt, act)


# ---------------------------------------------------------------------------
# hlo / timed (profile one representative block)


def _block_fn_and_args(cfg, batch: int, seq: int):
    from repro.models.layers import init_params
    from repro.models.transformer import block_apply, block_specs

    params = init_params(block_specs(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    x = jnp.zeros((batch, seq, cfg.d_model), jnp.dtype(cfg.compute_dtype))

    def f(p, x):
        y, aux, _, _ = block_apply(cfg, p, x)
        return y, aux

    return f, params, x


def _hlo_layer(cfg, batch: int, seq: int) -> LayerProfile:
    from repro.runtime.hlo_cost import analyze

    f, params, x = _block_fn_and_args(cfg, batch, seq)
    compiled = jax.jit(f).lower(params, x).compile()
    hc = analyze(compiled.as_text())
    pdt = jnp.dtype(cfg.param_dtype).itemsize
    cdt = jnp.dtype(cfg.compute_dtype).itemsize
    pbytes = sum(p.size for p in jax.tree.leaves(params)) * pdt
    return LayerProfile("block", float(hc["flops"]), float(pbytes),
                        float(batch * seq * cfg.d_model * cdt))


def _timed_layer(cfg, batch: int, seq: int, *, iters: int = 3
                 ) -> LayerProfile:
    f, params, x = _block_fn_and_args(cfg, batch, seq)
    jf = jax.jit(f)
    jax.block_until_ready(jf(params, x))       # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jf(params, x)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    base = _analytic_layer(cfg, batch, seq)
    return replace(base, time_s=dt)


METHODS = ("auto", "hlo", "timed", "analytic")


def profile_model(cfg, *, batch: int = 1, seq: int = 32,
                  method: str = "auto") -> ModelProfile:
    """Per-layer profile for an ArchConfig (one entry per layer)."""
    if method not in METHODS:
        raise ValueError(f"unknown profile method {method!r}")
    used, fallback = method, ""
    if method in ("auto", "hlo"):
        try:
            layer = _hlo_layer(cfg, batch, seq)
            used = "hlo"
        except Exception as e:
            if method == "hlo":
                raise
            layer = _analytic_layer(cfg, batch, seq)
            used = "analytic"
            fallback = f"hlo failed: {type(e).__name__}: {e}"
    elif method == "timed":
        layer = _timed_layer(cfg, batch, seq)
    else:
        layer = _analytic_layer(cfg, batch, seq)
    layers = tuple(replace(layer, name=f"block{j}")
                   for j in range(cfg.n_layers))
    return ModelProfile(cfg.name, used, batch, seq, layers, fallback)
