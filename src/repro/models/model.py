"""Model assembly: param specs, reference forward, stage functions for the
pipeline runtimes, KV-cache/state decode, and dry-run input specs.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (embed_apply, embed_specs, norm_apply,
                                 norm_specs, sinusoidal_pos,
                                 softmax_xent, specs_to_axes, specs_to_sds,
                                 init_params, stack_specs, unembed_apply)
from repro.models.transformer import (block_apply, block_specs,
                                      shared_block_apply, shared_block_specs)

WHISPER_ENC_FRAMES = 1500  # fixed encoder context for decode shapes


def tree_slice(tree, idx):
    return jax.tree.map(lambda a: a[idx], tree)


def tree_slice_range(tree, lo, hi):
    return jax.tree.map(lambda a: a[lo:hi], tree)


def uniform_stage_sizes(n_layers: int, n_stages: int) -> Tuple[int, ...]:
    """Equal-count contiguous split, remainder spread over early stages
    (the same split :func:`repro.planner.partition.uniform` produces)."""
    if n_stages < 1 or n_layers < n_stages:
        raise ValueError(f"cannot split {n_layers} layers into "
                         f"{n_stages} stages (a stage would be empty)")
    base, rem = divmod(n_layers, n_stages)
    return tuple(base + (1 if s < rem else 0) for s in range(n_stages))


def flat_stage_layers(stages):
    """Merge stage layer params to a flat [L, ...] tree.

    Accepts the ragged canonical layout (tuple of per-stage trees,
    concatenated in stage order) and the legacy stacked
    ``[S, Lps, ...]`` dict layout (reshaped).  The single
    flat-layer-order routine — `Model.flat_layers` and
    `runtime/elastic` both delegate here."""
    if isinstance(stages, (tuple, list)):
        if len(stages) == 1:
            return stages[0]["layers"]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                            *[t["layers"] for t in stages])
    return jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), stages["layers"])


def split_flat_stages(flat_stages, sizes) -> Tuple[Any, ...]:
    """Flat ``{"layers": [L, ...](, "shared": [S, ...])}`` -> ragged
    per-stage trees for ``sizes`` (the one slicing-by-sizes routine —
    `Model.init`, `partition_stage_params` and `runtime/elastic` all
    route through it)."""
    out, lo = [], 0
    for k, n in enumerate(sizes):
        tree: Dict[str, Any] = {
            "layers": tree_slice_range(flat_stages["layers"], lo, lo + n)}
        if "shared" in flat_stages:
            tree["shared"] = tree_slice(flat_stages["shared"], k)
        out.append(tree)
        lo += n
    return tuple(out)


def pack_chunk_params(chunks, n_devices: int):
    """Ragged chunk trees -> the dense MPMD layout: every ``layers``
    leaf becomes ``[v, S, Lmax, ...]`` with chunk ``q`` at index
    ``[q // S, q % S]`` zero-padded to ``Lmax = max(sizes)`` rows.

    Sharding dim 1 with ``PartitionSpec(None, 'pipe')`` therefore pins
    chunk ``q`` wholly to pipe device ``q % S`` (Megatron round-robin
    folding) — the layout that lets one jitted program hold
    differently-sized stage trees stage-locally.  Reshaping dims 0–1 to
    ``[C, Lmax, ...]`` row-major recovers chunk order, which is flat
    layer order.  Returns ``(packed_tree, sizes)``; hybrid per-stage
    ``shared`` blocks have no layer stack to pad and are refused.
    """
    C = len(chunks)
    S = int(n_devices)
    if S < 1 or C % S:
        raise ValueError(f"{C} chunk trees do not fold onto {S} devices")
    if any("shared" in t for t in chunks):
        raise ValueError(
            "hybrid stage trees carry per-stage 'shared' blocks with no "
            "flat layer order; the packed MPMD layout does not cover them")
    sizes = tuple(int(jax.tree.leaves(t["layers"])[0].shape[0])
                  for t in chunks)
    Lmax = max(sizes)
    v = C // S

    def leaf(*xs):
        padded = [
            jnp.concatenate(
                [x, jnp.zeros((Lmax - x.shape[0],) + x.shape[1:], x.dtype)],
                0) if x.shape[0] < Lmax else x
            for x in xs]
        return jnp.stack(padded, 0).reshape((v, S, Lmax) + xs[0].shape[1:])

    packed = {"layers": jax.tree.map(leaf, *[t["layers"] for t in chunks])}
    return packed, sizes


def unpack_chunk_params(packed, sizes) -> Tuple[Any, ...]:
    """Inverse of :func:`pack_chunk_params`: dense ``[v, S, Lmax, ...]``
    leaves back to the ragged chunk trees (padding rows dropped)."""
    sizes = tuple(int(n) for n in sizes)
    C = len(sizes)

    def flat(a):
        if a.shape[0] * a.shape[1] != C:
            raise ValueError(
                f"packed leaf folds {a.shape[0] * a.shape[1]} chunks, "
                f"sizes cover {C}")
        return a.reshape((C,) + a.shape[2:])

    rows = jax.tree.map(flat, packed["layers"])
    return tuple({"layers": jax.tree.map(lambda a: a[q, :sizes[q]], rows)}
                 for q in range(C))


class Model:
    """Functional model wrapper for one ArchConfig.

    Stage parameters use the **ragged per-stage canonical layout**:
    ``params["stages"]`` is a tuple of ``n_stages`` pytrees whose
    ``layers`` leaves are ``[L_k, ...]`` with ``L_k`` from
    ``stage_sizes`` (the uniform split, remainder on early stages) —
    any ``(n_layers, n_stages)`` initializes, no divisibility required.
    The runtimes repartition these trees to a plan's sizes via
    :meth:`partition_stage_params`, which also still accepts the legacy
    stacked ``[S, Lps, ...]`` dict layout old checkpoints carry.
    """

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        plan = cfg.mesh_plan
        pipelineable = (plan.pipe_role == "stage" and plan.pipe > 1
                        and not cfg.is_encdec)
        self.n_stages = plan.pipe if pipelineable else 1
        self.stage_sizes = uniform_stage_sizes(cfg.n_layers, self.n_stages)
        self.hybrid = (cfg.ssm is not None and cfg.ssm.shared_attn_every > 0)

    @property
    def layers_per_stage(self) -> int:
        """Uniform per-stage layer count; only defined when the default
        split is uniform (legacy accessor — prefer ``stage_sizes``)."""
        if self.cfg.n_layers % self.n_stages:
            raise ValueError(
                f"{self.cfg.name}: {self.cfg.n_layers} layers over "
                f"{self.n_stages} stages is ragged "
                f"(sizes {self.stage_sizes}); use stage_sizes")
        return self.cfg.n_layers // self.n_stages

    # ------------------------------------------------------------------ specs
    def _outer_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        outer: Dict[str, Any] = {
            "embed": embed_specs(cfg),
            "ln_f": norm_specs(cfg),
        }
        if cfg.is_encdec:
            outer["ln_f_enc"] = norm_specs(cfg)
        return outer

    def param_specs(self) -> Dict[str, Any]:
        """Specs in the canonical layout: ragged per-stage tuple for
        pipelined stacks (``layers`` leaves ``[L_k, ...]``, one
        ``shared`` block per stage for hybrid models)."""
        cfg = self.cfg
        outer = self._outer_specs()
        if cfg.is_encdec:
            stages = {
                "enc": stack_specs(block_specs(cfg), cfg.n_enc_layers,
                                   "layer"),
                "dec": stack_specs(block_specs(cfg, cross=True),
                                   cfg.n_layers, "layer"),
            }
            return {"outer": outer, "stages": stages}
        layer = block_specs(cfg)
        stages = []
        for n in self.stage_sizes:
            tree: Dict[str, Any] = {"layers": stack_specs(layer, n, "layer")}
            if self.hybrid:
                tree["shared"] = shared_block_specs(cfg)
            stages.append(tree)
        return {"outer": outer, "stages": tuple(stages)}

    def _flat_param_specs(self) -> Dict[str, Any]:
        """Spec tree used for initialization: all layers in one
        ``[n_layers, ...]`` stack (hybrid shared blocks ``[S, ...]``).

        This is RNG-compatible with the pre-ragged stacked layout — a
        ``[S, Lps, ...]`` and an ``[L, ...]`` draw of the same spec leaf
        consume the same key and produce the same bits in layer order —
        so ragged canonical init stays bit-identical to historical
        (golden-pinned) initializations wherever the split is uniform.
        """
        cfg = self.cfg
        if cfg.is_encdec:
            return self.param_specs()
        stages: Dict[str, Any] = {
            "layers": stack_specs(block_specs(cfg), cfg.n_layers, "layer")}
        if self.hybrid:
            stages["shared"] = stack_specs(shared_block_specs(cfg),
                                           self.n_stages, "stage")
        return {"outer": self._outer_specs(), "stages": stages}

    def init(self, key, layers_sharding=None):
        """Random parameters in the canonical layout.  Under ``jit``,
        ``layers_sharding`` places the flat ``[n_layers, ...]`` layer
        stacks as they are drawn (the draw is partitionable, so each
        device then computes only its own rows, bit-identically)."""
        params = init_params(self._flat_param_specs(), key,
                             self.cfg.param_dtype)
        if self.cfg.is_encdec:
            return params
        if layers_sharding is not None:
            params["stages"]["layers"] = jax.lax.with_sharding_constraint(
                params["stages"]["layers"], layers_sharding)
        return {"outer": params["outer"],
                "stages": split_flat_stages(params["stages"],
                                            self.stage_sizes)}

    def param_sds(self):
        return specs_to_sds(self.param_specs(), self.cfg.param_dtype)

    def param_axes(self):
        return specs_to_axes(self.param_specs())

    # ------------------------------------------------------------ stage apply
    def _layer_body(self, *, pos_offset: int = 0,
                    remat: Optional[str] = None):
        cfg = self.cfg
        remat = cfg.remat if remat is None else remat

        def body(carry, layer_p):
            x, aux = carry
            x, a, _, _ = block_apply(cfg, layer_p, x, pos_offset=pos_offset)
            return (x, aux + a), None

        if remat == "full":
            body = jax.checkpoint(body)
        elif remat == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots)
        return body

    def stage_apply(self, stage_params, carry, *, pos_offset: int = 0,
                    remat: Optional[str] = None):
        """One pipeline stage: its blocks (+ hybrid shared block).

        The layer count is read off the param tree's leading axis, so the
        same code executes uniform stages and ragged (plan-partitioned)
        stages.  carry = (x [b,s,d], aux scalar).  ``remat`` overrides
        ``cfg.remat`` for the layer bodies (``"none"`` keeps every
        residual: a caller whose backward follows at once)."""
        cfg = self.cfg
        body = self._layer_body(pos_offset=pos_offset, remat=remat)
        layers = stage_params["layers"]
        if not self.hybrid:
            carry, _ = jax.lax.scan(body, carry, layers)
            return carry
        k = cfg.ssm.shared_attn_every
        n = jax.tree.leaves(layers)[0].shape[0]
        lo = 0
        while lo < n:
            hi = min(lo + k, n)
            carry, _ = jax.lax.scan(body, carry,
                                    tree_slice_range(layers, lo, hi))
            if hi < n or hi == n and lo + k == n:
                x, aux = carry
                x, _ = shared_block_apply(cfg, stage_params["shared"], x,
                                          pos_offset=pos_offset)
                carry = (x, aux)
            lo = hi
        return carry

    # ------------------------------------------------------- embed/head
    def embed(self, outer, batch):
        cfg = self.cfg
        if cfg.is_encdec:
            raise RuntimeError("use forward() for enc-dec")
        x = embed_apply(cfg, outer["embed"], batch["tokens"])
        if cfg.frontend == "vision" and "patches" in batch:
            patches = batch["patches"].astype(x.dtype)
            x = jax.lax.dynamic_update_slice(x, patches, (0, 0, 0))
        if cfg.pos_embed == "sinusoidal":
            x = x + sinusoidal_pos(x.shape[1], cfg.d_model, dtype=x.dtype)
        return x

    def head_loss(self, outer, x, targets):
        cfg = self.cfg
        x = norm_apply(cfg, outer["ln_f"], x)
        logits = unembed_apply(cfg, outer["embed"], x)
        return softmax_xent(logits, targets, cfg.vocab_size)

    def logits(self, outer, x):
        cfg = self.cfg
        x = norm_apply(cfg, outer["ln_f"], x)
        return unembed_apply(cfg, outer["embed"], x)

    # -------------------------------------------------- reference fwd
    def hidden(self, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Final hidden states (pre-head).  Returns (x, aux_loss)."""
        cfg = self.cfg
        outer, stages = params["outer"], params["stages"]
        if cfg.is_encdec:
            return self._hidden_encdec(params, batch)
        x = self.embed(outer, batch)
        carry = (x, jnp.zeros((), jnp.float32))
        for s in range(self.n_stages):
            sp = (stages[s] if isinstance(stages, (tuple, list))
                  else tree_slice(stages, s))
            carry = self.stage_apply(sp, carry)
        return carry

    def forward(self, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Full (non-pipelined) forward.  Returns (logits, aux_loss)."""
        x, aux = self.hidden(params, batch)
        return self.logits(params["outer"], x), aux

    def prefill_logits(self, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Serving prefill: last-position logits only."""
        x, aux = self.hidden(params, batch)
        return self.logits(params["outer"], x[:, -1:]), aux

    def encode(self, params, batch):
        """Encoder stack -> enc_out (enc-dec archs)."""
        cfg = self.cfg
        outer, stages = params["outer"], params["stages"]
        dt = jnp.dtype(cfg.compute_dtype)
        if cfg.frontend == "audio":
            enc_x = batch["frames"].astype(dt)
        else:
            enc_x = embed_apply(cfg, outer["embed"], batch["src_tokens"])
        enc_x = enc_x + sinusoidal_pos(enc_x.shape[1], cfg.d_model, dtype=dt)
        body = self._layer_body()

        def enc_body(carry, lp):
            (x, aux), _ = body(carry, lp)
            return (x, aux), None
        (enc_x, _), _ = jax.lax.scan(
            enc_body, (enc_x, jnp.zeros((), jnp.float32)), stages["enc"])
        return norm_apply(cfg, outer["ln_f_enc"], enc_x)

    def encdec_prefill_cache(self, params, batch, max_seq: int):
        """Run the encoder and precompute per-decoder-layer cross K/V."""
        cfg = self.cfg
        stages = params["stages"]
        enc_out = self.encode(params, batch)
        b, e_len = enc_out.shape[0], enc_out.shape[1]
        KV, hd = cfg.n_kv_heads, cfg.hd
        dt = enc_out.dtype

        def body(_, lp):
            ck = (enc_out @ lp["xattn"]["wk"].astype(dt)
                  ).reshape(b, e_len, KV, hd)
            cv = (enc_out @ lp["xattn"]["wv"].astype(dt)
                  ).reshape(b, e_len, KV, hd)
            return None, (ck, cv)
        _, (cks, cvs) = jax.lax.scan(body, None, stages["dec"])
        L = cfg.n_layers
        z = lambda *s: jnp.zeros(s, dt)
        return {
            "self": {"k": z(L, b, max_seq, KV, hd),
                     "v": z(L, b, max_seq, KV, hd)},
            "cross": {"k": cks, "v": cvs},
        }

    def _hidden_encdec(self, params, batch):
        cfg = self.cfg
        outer, stages = params["outer"], params["stages"]
        dt = jnp.dtype(cfg.compute_dtype)
        enc_out = self.encode(params, batch)
        aux = jnp.zeros((), jnp.float32)

        x = embed_apply(cfg, outer["embed"], batch["tokens"])
        x = x + sinusoidal_pos(x.shape[1], cfg.d_model, dtype=dt)

        def dec_body(carry, lp):
            x, aux = carry
            x, a, _, _ = block_apply(cfg, lp, x, enc_out=enc_out)
            return (x, aux + a), None
        if cfg.remat == "full":
            dec_body = jax.checkpoint(dec_body)
        (x, aux), _ = jax.lax.scan(dec_body, (x, aux), stages["dec"])
        return x, aux

    def loss(self, params, batch):
        logits, aux = self.forward(params, batch)
        return softmax_xent(logits, batch["targets"],
                            self.cfg.vocab_size) + aux

    # ------------------------------------------------------------------ decode
    def flat_layers(self, stages):
        """See :func:`flat_stage_layers` (ragged or legacy stacked)."""
        return flat_stage_layers(stages)

    @staticmethod
    def _stage_shared(stages, k):
        """Stage k's tied shared block in either layout (None if absent)."""
        if isinstance(stages, (tuple, list)):
            return stages[k].get("shared")
        if "shared" in stages:
            return tree_slice(stages["shared"], k)
        return None

    # --------------------------------------------------------- ragged stages
    def partition_stage_params(self, stages, sizes, *, n_chunks=None):
        """Regroup stage params into per-stage trees for ``sizes``.

        ``stages`` is either the ragged canonical tuple (any partition)
        or the legacy stacked layout (leaves [S, Lps, ...]); ``sizes``
        is a per-stage layer-count vector (a planner
        ``Partition.sizes()``), summing to ``cfg.n_layers``.  Returns a
        tuple of ``len(sizes)`` stage trees whose ``layers`` leaves are
        [sizes[k], ...] — the ragged layout the streaming runtime
        executes, realizing non-uniform (DP) plans.  A ragged input
        whose sizes already match is returned as-is.

        ``n_chunks``: expected tree count when it is not the model's
        device-stage count — interleaved/virtual-stage plans split the
        same layers into ``n_stages · v`` chunk-stages, each its own
        tree (device d then holds the chunk trees d, d+S, … — see
        :meth:`device_chunk_params`).  Hybrid models pin one shared
        block per *device*: chunking would hand sibling chunks copies
        of that tied block which per-chunk gradient updates then fork,
        so virtual stages are refused for hybrid models.
        """
        want = n_chunks if n_chunks is not None else self.n_stages
        ragged_in = isinstance(stages, (tuple, list))
        has_shared = ("shared" in stages[0]) if ragged_in else \
            ("shared" in stages)
        if sum(sizes) != self.cfg.n_layers:
            raise ValueError(f"partition sizes {tuple(sizes)} do not cover "
                             f"{self.cfg.n_layers} layers")
        if len(sizes) != want:
            raise ValueError(f"{len(sizes)} partition stages for "
                             f"{want} (chunk-)stages")
        if n_chunks is not None and n_chunks % self.n_stages:
            raise ValueError(f"{n_chunks} chunks do not fold onto "
                             f"{self.n_stages} devices")
        if want > self.n_stages and has_shared:
            raise ValueError(
                f"virtual stages ({want} chunks on {self.n_stages} "
                f"devices) are unsupported for hybrid models: the "
                f"per-device shared block is tied across a device's "
                f"chunks and independent chunk updates would fork it")
        if min(sizes) < 1:
            raise ValueError(f"empty stage in partition sizes {tuple(sizes)}")
        if ragged_in and has_shared and len(stages) != want:
            raise ValueError(
                f"cannot repartition {len(stages)} hybrid stage trees "
                f"into {want}: shared blocks are tied per stage")
        if ragged_in:
            got = tuple(jax.tree.leaves(t["layers"])[0].shape[0]
                        for t in stages)
            if got == tuple(sizes):
                return tuple(stages)
        out = split_flat_stages({"layers": self.flat_layers(stages)}, sizes)
        if has_shared:
            # shared blocks stay with their stage index (tied per
            # stage, no flat layer order): ragged input passes trees
            # through, stacked input slices the [S, ...] stack
            out = tuple(
                {**t, "shared": (stages[k]["shared"] if ragged_in
                                 else tree_slice(stages["shared"], k))}
                for k, t in enumerate(out))
        return out

    def device_chunk_params(self, chunk_trees, n_devices=None):
        """Group chunk-stage trees by hosting device.

        ``chunk_trees`` is :meth:`partition_stage_params` output with
        ``C = n_devices · v`` trees; device ``d`` hosts chunk-stages
        ``d, d+S, …`` (Megatron round-robin placement), so the result is
        a tuple of ``n_devices`` tuples of ``v`` trees — the layout a
        real multi-device deployment materializes per device.
        """
        S = n_devices if n_devices is not None else self.n_stages
        C = len(chunk_trees)
        if S < 1 or C % S:
            raise ValueError(f"{C} chunk trees do not fold onto {S} devices")
        v = C // S
        return tuple(tuple(chunk_trees[c * S + d] for c in range(v))
                     for d in range(S))

    def stack_stage_params(self, stage_trees):
        """Inverse of :meth:`partition_stage_params` for uniform sizes:
        per-stage trees back to the canonical stacked [S, Lps, ...]
        layout (requires equal layer counts)."""
        sizes = {jax.tree.leaves(t["layers"])[0].shape[0]
                 for t in stage_trees}
        if len(sizes) != 1:
            raise ValueError("cannot stack ragged stages "
                             f"(sizes {sorted(sizes)}); uniform only")
        out: Dict[str, Any] = {"layers": jax.tree.map(
            lambda *xs: jnp.stack(xs, 0), *[t["layers"]
                                            for t in stage_trees])}
        if "shared" in stage_trees[0]:
            out["shared"] = jax.tree.map(
                lambda *xs: jnp.stack(xs, 0), *[t["shared"]
                                                for t in stage_trees])
        return out

    def ragged_stage_axes(self, n_stages: int):
        """Logical-axis pytree matching :meth:`partition_stage_params`
        output: one per-stage axes tree repeated ``n_stages`` times
        ('layer' names each stage tree's leading dim; there is no
        'stage' axis — placement of the differently-shaped stage trees
        is per-stage/MPMD, expressed by
        ``runtime.sharding.stage_placement_shardings`` rather than a
        PartitionSpec)."""
        one = self.param_axes()["stages"][0]
        return tuple(one for _ in range(n_stages))

    def init_cache(self, batch: int, max_seq: int, *,
                   stage_sizes: Optional[Sequence[int]] = None):
        """``stage_sizes``: the partition of the params that will be
        decoded (defaults to the model's canonical split).  Only hybrid
        models depend on it — their shared-attention cache has one slot
        per *full* ``shared_attn_every`` segment of each stage, so a
        plan-partitioned hybrid tree needs a cache built for the same
        partition."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.compute_dtype)
        L = cfg.n_layers
        if cfg.is_encdec:
            KV, hd = cfg.n_kv_heads, cfg.hd
            E = WHISPER_ENC_FRAMES
            z = lambda *s: jnp.zeros(s, dt)
            return {
                "self": {"k": z(L, batch, max_seq, KV, hd),
                         "v": z(L, batch, max_seq, KV, hd)},
                "cross": {"k": z(L, batch, E, KV, hd),
                          "v": z(L, batch, E, KV, hd)},
            }
        if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
            one = ssm_mod.rwkv6_init_state(cfg, batch, dt)
            return {"layers": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (L,) + a.shape), one)}
        if cfg.ssm is not None:
            one = ssm_mod.mamba2_init_state(cfg, batch, dt)
            cache = {"layers": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (L,) + a.shape), one)}
            if self.hybrid:
                kv = attn_mod.gqa_init_cache(cfg, batch, max_seq, dt)
                # exactly the slots decode consumes: stage_apply /
                # _decode_hybrid apply a stage's shared block once per
                # *full* k-layer segment, i.e. floor(L_s / k) times (a
                # stage shorter than k never applies it); keep >= 1
                # slot so the cache tree stays constructible — decode
                # then returns it untouched
                sizes = (self.stage_sizes if stage_sizes is None
                         else tuple(stage_sizes))
                n_shared = max(1, sum(
                    n // cfg.ssm.shared_attn_every for n in sizes))
                cache["shared"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (n_shared,) + a.shape), kv)
            return cache
        one = attn_mod.attn_init_cache(cfg, batch, max_seq, dt)
        return {"layers": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L,) + a.shape), one)}

    def decode_step(self, params, cache, token, pos):
        """token [b,1] int32, pos scalar -> (logits [b,1,V'], cache)."""
        cfg = self.cfg
        outer, stages = params["outer"], params["stages"]
        x = embed_apply(cfg, outer["embed"], token)
        if cfg.pos_embed == "sinusoidal":
            d = cfg.d_model
            ang = (pos.astype(jnp.float32) /
                   jnp.power(10000.0, jnp.arange(0, d, 2, jnp.float32) / d))
            pe = jnp.zeros((d,), jnp.float32).at[0::2].set(jnp.sin(ang))
            pe = pe.at[1::2].set(jnp.cos(ang))
            x = x + pe.astype(x.dtype)

        if cfg.is_encdec:
            return self._decode_encdec(params, cache, x, pos)

        if cfg.ssm is not None and not self.hybrid:
            def body(x, inp):
                lp, st = inp
                x, _, _, new_st = block_apply(cfg, lp, x, state=st)
                return x, new_st
            x, new_states = jax.lax.scan(
                body, x, (self.flat_layers(stages), cache["layers"]))
            return self.logits(outer, x), {"layers": new_states}

        if self.hybrid:
            return self._decode_hybrid(params, cache, x, pos)

        def body(x, inp):
            lp, lc = inp
            x, _, new_c, _ = block_apply(cfg, lp, x, cache=lc, pos=pos)
            return x, new_c
        x, new_cache = jax.lax.scan(
            body, x, (self.flat_layers(stages), cache["layers"]))
        return self.logits(outer, x), {"layers": new_cache}

    def stage_sizes_of(self, stages) -> Tuple[int, ...]:
        """The per-stage layer counts a stage-param tree actually
        carries (the model's default for the legacy stacked layout)."""
        if isinstance(stages, (tuple, list)):
            return tuple(jax.tree.leaves(t["layers"])[0].shape[0]
                         for t in stages)
        return tuple(self.stage_sizes)

    def _decode_hybrid(self, params, cache, x, pos):
        cfg = self.cfg
        outer, stages = params["outer"], params["stages"]
        k = cfg.ssm.shared_attn_every
        flat = self.flat_layers(stages)
        new_ssm, new_shared = [], []
        shared_idx = 0
        lo_g = 0
        # segment by the tree's ACTUAL partition, exactly like
        # stage_apply does in training — a plan-partitioned hybrid tree
        # must decode with the same shared-block positions it trained
        # with (the cache must be built for the same partition; see
        # init_cache's stage_sizes parameter)
        for s, L_s in enumerate(self.stage_sizes_of(stages)):
            lo = 0
            while lo < L_s:
                hi = min(lo + k, L_s)

                def body(x, inp):
                    lp, st = inp
                    x, _, _, new_st = block_apply(cfg, lp, x, state=st)
                    return x, new_st
                seg = (tree_slice_range(flat, lo_g + lo, lo_g + hi),
                       tree_slice_range(cache["layers"], lo_g + lo, lo_g + hi))
                x, st = jax.lax.scan(body, x, seg)
                new_ssm.append(st)
                if hi < L_s or lo + k == L_s:
                    sc = tree_slice(cache["shared"], shared_idx)
                    x, nc = shared_block_apply(
                        cfg, self._stage_shared(stages, s), x, pos=pos,
                        cache=sc)
                    new_shared.append(nc)
                    shared_idx += 1
                lo = hi
            lo_g += L_s
        cat = lambda *ts: jnp.concatenate(ts, 0)
        new_cache = {
            "layers": jax.tree.map(cat, *new_ssm),
            "shared": jax.tree.map(lambda *ts: jnp.stack(ts, 0), *new_shared)
            if new_shared else cache["shared"],
        }
        return self.logits(outer, x), new_cache

    def _decode_encdec(self, params, cache, x, pos):
        cfg = self.cfg
        outer, stages = params["outer"], params["stages"]

        def body(x, inp):
            lp, sc, ck, cv = inp
            xn = norm_apply(cfg, lp["ln1"], x)
            h, new_sc = attn_mod.gqa_apply(cfg, lp["attn"], xn,
                                           cache=sc, pos=pos)
            x = x + h
            # cross-attn against precomputed enc K/V
            xq = norm_apply(cfg, lp["lnx"], x)
            dt = x.dtype
            b = x.shape[0]
            H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
            q = (xq @ lp["xattn"]["wq"].astype(dt)).reshape(b, 1, H, hd)
            from repro.models.attention import _attend
            o = _attend(cfg, q, ck.astype(dt), cv.astype(dt), causal=False,
                        q_pos=jnp.zeros((1,), jnp.int32), k_len=ck.shape[1])
            x = x + o.reshape(b, 1, H * hd) @ lp["xattn"]["wo"].astype(dt)
            from repro.models.layers import mlp_apply
            x = x + mlp_apply(cfg, lp["mlp"],
                              norm_apply(cfg, lp["ln2"], x))
            return x, new_sc

        x, new_self = jax.lax.scan(
            body, x, (stages["dec"], cache["self"],
                      cache["cross"]["k"], cache["cross"]["v"]))
        return self.logits(outer, x), {"self": new_self,
                                       "cross": cache["cross"]}

    def prefill(self, params, batch, max_seq: int):
        """Full forward building a decode cache (attention archs)."""
        cfg = self.cfg
        outer, stages = params["outer"], params["stages"]
        if cfg.is_encdec or self.hybrid or cfg.ssm is not None:
            # handled by specialised paths / tests use decode from scratch
            logits, aux = self.forward(params, batch)
            return logits, None
        x = self.embed(outer, batch)
        s = x.shape[1]

        def body(carry, lp):
            x, aux = carry
            x, a, new_c, _ = block_apply(cfg, lp, x, cache={})
            return (x, aux + a), new_c
        (x, aux), caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), self.flat_layers(stages))
        full = self.init_cache(x.shape[0], max_seq)
        placed = jax.tree.map(
            lambda buf, got: jax.lax.dynamic_update_slice(
                buf, got.astype(buf.dtype), (0,) * buf.ndim),
            full["layers"], caches)
        return self.logits(outer, x), {"layers": placed}

    # ------------------------------------------------------- pipelined serve
    def decode_embed(self, outer, tokens, pos):
        """Embed decode tokens with per-position encodings.

        ``tokens`` is [b, s] int32; ``pos`` is int32 *broadcastable to*
        ``tokens.shape`` (the decode wave passes [R, 1] per-request
        positions, a prefill lane [1, P] = ``arange(P)``).  Elementwise
        this is exactly :meth:`decode_step`'s embed + sinusoidal term,
        so pipelined serving stays bitwise-identical to whole-model
        decoding."""
        cfg = self.cfg
        x = embed_apply(cfg, outer["embed"], tokens)
        if cfg.pos_embed == "sinusoidal":
            d = cfg.d_model
            p = jnp.asarray(pos, jnp.float32)
            ang = (p[..., None] /
                   jnp.power(10000.0, jnp.arange(0, d, 2, jnp.float32) / d))
            pe = jnp.zeros(p.shape + (d,), jnp.float32)
            pe = pe.at[..., 0::2].set(jnp.sin(ang))
            pe = pe.at[..., 1::2].set(jnp.cos(ang))
            x = x + pe.astype(x.dtype)
        return x

    def stage_decode(self, stage_params, stage_cache, x, pos):
        """One chunk-stage's single-token decode: x [b, 1, d], scalar
        ``pos`` -> (x [b, 1, d], new stage cache).  ``stage_params`` is
        one :meth:`partition_stage_params` chunk tree, ``stage_cache``
        the matching slice of an :meth:`init_cache` tree.  Scanning the
        stage's layers with :meth:`decode_step`'s per-layer bodies keeps
        the per-(layer, token) op sequence — and therefore the emitted
        tokens — bitwise-identical to whole-model decoding."""
        cfg = self.cfg
        if cfg.is_encdec or self.hybrid:
            kind = "encoder-decoder" if cfg.is_encdec else "hybrid"
            raise NotImplementedError(
                f"stage_decode does not support {kind} models "
                f"({cfg.name}): their decode state is not a per-layer "
                f"scan (cross-attention / tied shared blocks); serve "
                f"them with launch/serve.py's whole-model SimpleEngine")
        layers = stage_params["layers"]
        if cfg.ssm is not None:
            def body(x, inp):
                lp, st = inp
                x, _, _, new_st = block_apply(cfg, lp, x, state=st)
                return x, new_st
            x, new_states = jax.lax.scan(
                body, x, (layers, stage_cache["layers"]))
            return x, {"layers": new_states}

        def body(x, inp):
            lp, lc = inp
            x, _, new_c, _ = block_apply(cfg, lp, x, cache=lc, pos=pos)
            return x, new_c
        x, new_cache = jax.lax.scan(
            body, x, (layers, stage_cache["layers"]))
        return x, {"layers": new_cache}

    def stage_prefill(self, stage_params, stage_cache, x_seq, n_valid):
        """One chunk-stage's whole-prompt prefill in a single call:
        x_seq [1, P, d] -> (y_seq [1, P, d], new stage cache).

        Scans :meth:`stage_decode` over positions 0..P-1 inside one
        XLA computation (one Python dispatch per *chunk*, not per
        token); positions >= ``n_valid`` compute on padding but their
        cache updates are masked out, so the final cache equals a
        token-by-token prefill of exactly the first ``n_valid`` tokens
        from ``stage_cache`` — pass a fresh init slice to keep a
        recycled KV page from leaking its previous request's state."""
        P = x_seq.shape[1]

        def body(cache, i):
            x = jax.lax.dynamic_slice_in_dim(x_seq, i, 1, 1)
            y, new_c = self.stage_decode(stage_params, cache, x, i)
            keep = i < n_valid
            new_c = jax.tree.map(
                lambda o, n: jnp.where(keep, n.astype(o.dtype), o),
                cache, new_c)
            return new_c, y

        new_cache, ys = jax.lax.scan(
            body, stage_cache, jnp.arange(P, dtype=jnp.int32))
        return jnp.swapaxes(ys[:, :, 0, :], 0, 1), new_cache


# ===========================================================================
# cache logical axes (for decode-cell sharding)
# ===========================================================================


def cache_axes(model: "Model"):
    """Logical-axis pytree mirroring ``init_cache`` output structure."""
    cfg = model.cfg
    gqa_ax = {"k": ("layer", "act_batch", "act_kvseq", "kv", "head_dim"),
              "v": ("layer", "act_batch", "act_kvseq", "kv", "head_dim")}
    if cfg.is_encdec:
        return {"self": gqa_ax, "cross": gqa_ax}
    if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
        return {"layers": {
            "x_tm": ("layer", "act_batch", "heads"),
            "x_cm": ("layer", "act_batch", "heads"),
            "S": ("layer", "act_batch", "heads", "head_dim", "head_dim"),
        }}
    if cfg.ssm is not None:
        ax = {"layers": {
            "conv_x": ("layer", "act_batch", None, "ssm"),
            "conv_bc": ("layer", "act_batch", None, None),
            "S": ("layer", "act_batch", "heads", "head_dim", "state"),
        }}
        if model.hybrid:
            ax["shared"] = gqa_ax
        return ax
    if cfg.mla is not None:
        return {"layers": {
            "c_kv": ("layer", "act_batch", "act_kvseq", None),
            "k_rope": ("layer", "act_batch", "act_kvseq", None),
        }}
    return {"layers": gqa_ax}


# ===========================================================================
# dry-run input specs
# ===========================================================================


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    model = Model(cfg)
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    cdt = jnp.dtype(cfg.compute_dtype)
    tok = lambda *s: jax.ShapeDtypeStruct(s, i32)

    if shape.kind in ("train", "prefill"):
        batch: Dict[str, Any] = {"tokens": tok(B, S)}
        if shape.kind == "train":
            batch["targets"] = tok(B, S)
        if cfg.frontend == "audio":
            batch["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), cdt)
        if cfg.frontend == "vision":
            batch["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.frontend_patches, cfg.d_model), cdt)
        return {"batch": batch}

    # decode: one token against a seq_len cache
    cache = jax.eval_shape(lambda: model.init_cache(B, S))
    return {"cache": cache, "token": tok(B, 1),
            "pos": jax.ShapeDtypeStruct((), i32)}
