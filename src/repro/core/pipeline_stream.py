"""Async streaming pipeline — the paper's PipeDream-style runtime.

One ``train_step`` call = one pipeline **tick**.  Every stage performs one
forward (of the microbatch injected ``k`` ticks ago) and one backward (of
the microbatch injected ``2(S−1)−k`` ticks ago) per tick; in-flight
activations/cotangents live in ring buffers carried across steps inside
the train state.  Each stage applies its own gradient the tick its
backward completes — per-minibatch, per-stage weight updates, i.e. exactly
the staleness structure of §3.1.  After the 2(S−1)-tick warm-up there is
**zero bubble**.

Weight-handling modes (§3.2 / Fig. 7):

  vanilla    fwd & bwd use current weights            (stale, inconsistent)
  pipedream  fwd uses current, bwd the stashed fwd weights (stale, consistent)
  spectrain  fwd uses Ŵ = W − s_fwd·η·v (Eq. 4 with s_fwd = 2(S−1−k));
             bwd uses current weights (s_bwd = 0 → already the target)

Gradient synchronization over the `data` (and `pod`) mesh axes is inserted
by GSPMD from the sharding specs — synchronous DP across replicas, async
across pipeline stages, exactly the paper's hybrid.

Backward uses stored stage *inputs* plus recompute (remat), so the rings
hold one activation tensor per (stage, in-flight microbatch) — the same
memory PipeDream's activation stashing pays, and ~L× less than storing
residuals.  The exception is a stage whose backward runs in the tick of
its own forward and at that forward's weights (the last stage, unless
``pipedream``; :func:`forward_reuse_stages`): it takes its vjp in the
forward, with every residual kept, and its backward applies that vjp
instead of running the forward again.

Stage parameters are **ragged per-stage trees**: ``state["params"]
["stages"]`` is a tuple of ``S`` pytrees whose ``layers`` leaves are
``[L_k, ...]`` for the plan's per-stage layer counts — the same ragged
canonical layout ``Model.init`` produces (no ``n_layers % n_stages``
constraint anywhere).  Activations are ``d_model``-wide at every cut,
so the rings stay uniform ``[S, ...]`` arrays — only weights (and
their momentum/stash/prediction mirrors) go ragged.  A planner
``PipelinePlan`` with a non-uniform (DP) partition is therefore
*executed*, not just logged: ``make_state`` repartitions the canonical
trees via ``Model.partition_stage_params`` (a no-op when the plan's
sizes match; legacy stacked ``[S, Lps, ...]`` inputs are accepted and
regrouped) and validates the plan's layer ranges against the model.

Besides the streaming tick loop above, this module hosts an
**IR-interpreter runtime** (``make_ir_state`` / ``make_ir_train_step``)
executing the planner's round-based schedule families — GPipe, 1F1B
(PipeDream-flush), PipeDream-2BW, and interleaved/virtual-stage 1F1B.
One ``train_step`` call is one flush round (or 2BW accumulation group):
the step walks the IR's compute events in timeline order instead of a
hard-coded fill/steady/drain structure, so the control flow is the
schedule.  Two interchangeable round bodies exist (``backend=``):
the default ``"scan"`` lowers the round to the planner's dense
:class:`~repro.planner.schedule_ir.EventTable` and runs a ``lax.scan``
over its rows with ``lax.switch`` dispatch per (opcode, chunk, lag) —
trace size O(#branches) ≤ O(2·n_chunks), independent of the round's
microbatch count; ``"unrolled"`` inlines every event into the trace
(the original interpreter, kept as the reference oracle the scan
backend is tested bit-identical against).  Per-event weight reads
resolve through the IR — flush
schedules read current weights (their derived staleness is 0), 2BW
reads the previous version from a weight stash whose depth comes from
``Schedule.weight_stash_depth`` (2, the "double buffer"), and
``spectrain`` mode predicts each read forward by that event's derived
version lag (Eq. 4 with s from the IR, not a closed form).  Virtual
stages make ``params["stages"]`` a tuple of ``n_chunks = S·v`` chunk
trees; device d of the S devices hosts chunks ``d, d+S, …``
(``Model.device_chunk_params``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import spectrain as st
from repro.models.layers import shard_act
from repro.obs import phases
from repro.optim import sgd

MODES = ("vanilla", "pipedream", "spectrain")


def _plan_vectors(S: int, plan):
    """(s_fwd, bwd_lag, fb_gap) per stage — from a planner
    ``PipelinePlan`` when given (IR-derived), else the closed-form
    streaming schedule.

    ``s_fwd``   prediction distance (updates between fwd read and the
                minibatch's own update) — Eq. 4's s;
    ``bwd_lag`` injection→backward ticks, 2(S−1)−k — gates warm-up
                validity and the stage-0 batch-ring read;
    ``fb_gap``  same-stage fwd→backward ticks, 2(S−1−k) — the stash-ring
                gather offsets.

    The runtime's dataflow (one fwd/bwd wave per tick, ring rotation) IS
    the stream schedule, so only stream plans are accepted; the planner
    derives the same vectors by walking IR events, which turns the
    constants below into a checked property.
    """
    if plan is None:
        return ([st.version_difference_stream(k, S, "forward")
                 for k in range(S)],
                [2 * (S - 1) - k for k in range(S)],
                [2 * (S - 1 - k) for k in range(S)])
    if plan.schedule != "stream":
        raise ValueError(
            f"pipeline_stream executes the stream schedule, got a "
            f"{plan.schedule!r} plan (use core.simulator for those)")
    if plan.n_stages != S:
        raise ValueError(f"plan has {plan.n_stages} stages, model has {S}")
    return list(plan.s_fwd), list(plan.bwd_lag), list(plan.fb_gap)


def stage_sizes(model, plan) -> Tuple[int, ...]:
    """Per-stage layer counts this runtime executes.

    Without a plan: the uniform split the model was initialized with.
    With a plan: the plan's partition, validated as an *executable*
    artifact — its layer ranges must tile exactly the model's layers
    across exactly the model's stages (a plan built against a different
    model fails here rather than silently mis-slicing weights).
    """
    S = model.n_stages
    if plan is None:
        return tuple(model.stage_sizes)
    part = plan.partition
    if part.n_stages != plan.n_stages:
        raise ValueError(f"plan partition has {part.n_stages} stages but "
                         f"plan.n_stages={plan.n_stages}")
    if part.n_layers != model.cfg.n_layers:
        raise ValueError(
            f"plan partitions {part.n_layers} layers, model has "
            f"{model.cfg.n_layers}")
    sizes = part.sizes()
    if len(sizes) != S:
        raise ValueError(f"plan has {len(sizes)} stages, model has {S}")
    if min(sizes) < 1:
        raise ValueError(f"plan has an empty stage: sizes={sizes}")
    return sizes


def _ring_write(ring, idx, val):
    """ring leaves [R, ...]; write val at slot idx (traced scalar)."""
    return jax.tree.map(
        lambda r, v: jax.lax.dynamic_update_index_in_dim(
            r, v.astype(r.dtype), idx, 0), ring, val)


def _ring_read(ring, idx):
    return jax.tree.map(
        lambda r: jax.lax.dynamic_index_in_dim(r, idx, 0, keepdims=False),
        ring)


def _per_stage_gather(ring, idx_vec):
    """ring leaves [S, R, ...]; gather slot idx_vec[k] for each stage k."""
    def leaf(r):
        return jax.vmap(
            lambda rk, i: jax.lax.dynamic_index_in_dim(rk, i, 0, False)
        )(r, idx_vec)
    return jax.tree.map(leaf, ring)


def _predict_stages(stage_trees, mom_trees, lr, s_fwd_v, skip=()):
    """Eq. 4 per stage tree with that stage's (python int) distance;
    ``None`` for the stages in ``skip``."""
    out = []
    for k, (w, v, s) in enumerate(zip(stage_trees, mom_trees, s_fwd_v)):
        if k in skip:
            out.append(None)
            continue
        with phases.stage(k):
            out.append(st.predict_weights(w, v, lr, s))
    return tuple(out)


def forward_reuse_stages(S: int, mode: str, plan=None) -> Tuple[int, ...]:
    """Stages whose backward applies the vjp their own forward took.

    Such a stage runs its backward in the tick of its forward
    (``fb_gap`` 0, so the stashed input is the one the forward just
    took) and at the weights that forward read: the current ones, in
    ``vanilla`` mode or under a prediction of distance 0.  ``pipedream``
    reads its backward weights from the weight stash, so none of its
    stages qualifies.  For the stream schedule this is the last stage."""
    if S == 1 or mode == "pipedream":
        return ()
    s_fwd, _lag, gap = _plan_vectors(S, plan)
    return tuple(k for k in range(S)
                 if gap[k] == 0 and (mode == "vanilla" or s_fwd[k] == 0))


def make_state(model, params, batch_sds, *, mode: str = "spectrain",
               ticks_per_step: int = 1,
               fused_predict: bool = False, plan=None) -> Dict[str, Any]:
    """Streaming train state: params + momentum + in-flight rings.

    ``params`` is the ragged canonical init layout (legacy stacked
    ``[S, Lps, ...]`` trees are accepted too); for S > 1 its stage
    weights are repartitioned to the plan's sizes (the model's default
    split without a plan) — see module docstring.

    ``ticks_per_step``: the global batch is split into this many per-tick
    minibatches; one train_step runs that many ticks via lax.scan (the
    paper injects one minibatch per time unit).

    ``fused_predict``: store the next tick's predicted weights (bf16),
    computed inside the update pass (the kernels/fused_update schedule):
    identical math, but the prediction costs no extra HBM pass and the
    forward reads 2-byte weights."""
    cfg = model.cfg
    S = model.n_stages
    if S == 1:
        return {
            "params": params,
            "momentum": sgd.init(params).v,
            "step": jnp.zeros((), jnp.int32),
        }
    # _plan_vectors and stage_sizes validate the plan (stream schedule,
    # stage count, layer coverage) so a mismatched plan fails here rather
    # than under-sizing the rings or mis-slicing the stage weights that a
    # (plan-less or otherwise) train step later indexes.
    _, lag, gap = _plan_vectors(S, plan)
    sizes = stage_sizes(model, plan)
    params = {"outer": params["outer"],
              "stages": model.partition_stage_params(params["stages"],
                                                     sizes)}
    state: Dict[str, Any] = {
        "params": params,
        "momentum": sgd.init(params).v,
        "step": jnp.zeros((), jnp.int32),
    }
    if fused_predict and mode == "spectrain":
        cdt = jnp.dtype(cfg.compute_dtype)
        state["pred"] = {
            "outer": jax.tree.map(lambda p: p.astype(cdt), params["outer"]),
            "stages": tuple(
                jax.tree.map(lambda p: p.astype(cdt), t)
                for t in params["stages"]),
        }
    R = max(max(lag), max(gap)) + 1
    tok_sds = batch_sds["tokens"]
    B, seq = tok_sds.shape[0], tok_sds.shape[1]
    if B % ticks_per_step:
        raise ValueError(f"global batch {B} not divisible by "
                         f"ticks_per_step={ticks_per_step}")
    mb = B // ticks_per_step
    d = cfg.d_model
    cdt = jnp.dtype(cfg.compute_dtype)
    state.update({
        "tick": jnp.zeros((), jnp.int32),
        "fwd_buf": jnp.zeros((S, mb, seq, d), cdt),
        "bwd_buf": jnp.zeros((S, mb, seq, d), cdt),
        "stash_x": jnp.zeros((S, R, mb, seq, d), cdt),
        "batch_ring": jax.tree.map(
            lambda s: jnp.zeros((R, mb) + tuple(s.shape[1:]), s.dtype),
            batch_sds),
    })
    if mode == "pipedream":
        # per-stage weight rings: leaves [R, ...] mirroring each ragged
        # stage tree (the stacked layout had a single [S, R, ...] ring)
        state["w_stash"] = tuple(
            jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (R,) + p.shape),
                t)
            for t in params["stages"])
    return state


def init_state(model, key, batch_sds, *, mode: str = "spectrain",
               ticks_per_step: int = 1, plan=None):
    return make_state(model, model.init(key), batch_sds, mode=mode,
                      ticks_per_step=ticks_per_step, plan=plan)


def make_train_step(model, *, mode: str = "spectrain", lr: float,
                    gamma: float = 0.9, clip: Optional[float] = None,
                    ticks_per_step: int = 1, fused_predict: bool = False,
                    bwd_dtype: Optional[str] = None, plan=None) -> Callable:
    """``fused_predict``: prediction computed inside the update pass and
    stored bf16 (see make_state) — same math, one less weight pass/tick.
    ``bwd_dtype``: linearize the backward at weights cast to this dtype
    (e.g. "bfloat16") — gradients and their data-axis all-reduce then move
    half the bytes (standard mixed-precision training).
    ``plan``: optional ``repro.planner.PipelinePlan`` (stream schedule);
    supplies the IR-derived prediction distances and ring offsets in
    place of the closed-form constants, and its partition (validated by
    ``make_state``) determines the ragged stage trees this step
    executes.

    The stages of :func:`forward_reuse_stages` run their forward once:
    at the current weights (cast to ``bwd_dtype`` where set), without
    remat, keeping the vjp that their backward applies."""
    assert mode in MODES, mode
    fused_predict = fused_predict and mode == "spectrain"
    S = model.n_stages
    s_fwd_v, bwd_lag, fb_gap = _plan_vectors(S, plan)
    if plan is not None:
        stage_sizes(model, plan)   # fail fast on an unexecutable plan
    reuse = forward_reuse_stages(S, mode, plan)
    R = max(max(bwd_lag), max(fb_gap)) + 1
    s_fwd_embed = float(s_fwd_v[0])
    g_vec = jnp.array(fb_gap, jnp.int32)       # stash gather offsets
    lag_vec = jnp.array(bwd_lag, jnp.int32)    # injection -> bwd ticks
    s_fwd_v = [float(s) for s in s_fwd_v]

    def stage_fn(sp, xk):
        xk, aux = model.stage_apply(sp, (xk, jnp.zeros((), jnp.float32)))
        return xk, aux

    def stage_fn_keep(sp, xk):
        return model.stage_apply(sp, (xk, jnp.zeros((), jnp.float32)),
                                 remat="none")

    def bwd_weights(tree):
        if bwd_dtype is None:
            return tree
        bdt = jnp.dtype(bwd_dtype)
        return jax.tree.map(lambda p: p.astype(bdt), tree)

    # ------------------------------------------------------------- S == 1
    def step_degenerate(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch))(state["params"])
        with phases.scope("update"):
            if clip:
                grads, _ = sgd.clip_by_global_norm(grads, clip)
            params, mom = sgd.update(state["params"],
                                     sgd.MomentumState(state["momentum"]),
                                     grads, lr=lr, gamma=gamma)
        return ({**state, "params": params, "momentum": mom.v,
                 "step": state["step"] + 1},
                {"loss": loss, "loss_valid": jnp.ones((), jnp.float32)})

    if S == 1:
        return step_degenerate

    # ------------------------------------------------------------- S > 1
    def tick_fn(state: Dict[str, Any], batch):
        t = state["tick"]
        params, mom = state["params"], state["momentum"]
        outer, stages = params["outer"], params["stages"]
        mom_outer, mom_stages = mom["outer"], mom["stages"]

        # ---------- forward weights (Eq. 4) ------------------------------
        if fused_predict:
            # prediction was produced by the previous tick's update pass
            stages_f = state["pred"]["stages"]
            outer_embed_f = state["pred"]["outer"]
        elif mode == "spectrain":
            with phases.scope("predict"):
                stages_f = _predict_stages(stages, mom_stages, lr, s_fwd_v,
                                           skip=reuse)
                outer_embed_f = st.predict_weights(outer, mom_outer, lr,
                                                   s_fwd_embed)
        else:
            stages_f, outer_embed_f = stages, outer

        # ---------- inject + forward all stages --------------------------
        with phases.scope("forward"):
            x_new = model.embed(outer_embed_f, batch)
            A = state["fwd_buf"].at[0].set(x_new)
            A = shard_act(A, "stage", "act_batch", None, None)
            outs, kept_vjp = [], {}
            for k in range(S):
                with phases.stage(k):
                    if k in reuse:
                        o, kept_vjp[k] = jax.vjp(
                            stage_fn_keep, bwd_weights(stages[k]), A[k])
                        outs.append(o)
                    else:
                        outs.append(stage_fn(stages_f[k], A[k]))
            out = jnp.stack([o for o, _aux in outs])

        with phases.scope("transfer"):
            slot = jnp.mod(t, R)
            stash = jax.lax.dynamic_update_index_in_dim(
                state["stash_x"], A, slot, 1)
            batch_ring = _ring_write(state["batch_ring"], slot, batch)

        # ---------- head loss at the last stage ---------------------------
        with phases.scope("head"):
            valid_head = (t >= (S - 1)).astype(jnp.float32)
            tgt = _ring_read(batch_ring, jnp.mod(t - (S - 1), R))["targets"]

            loss, head_vjp = jax.vjp(
                lambda outer_, xlast: model.head_loss(outer_, xlast, tgt),
                outer, out[S - 1])
            g_outer_head, cot_last = head_vjp(valid_head)

        # ---------- backward all stages ------------------------------------
        with phases.scope("backward"):
            valid_b = ((t - lag_vec) >= 0)
            B_cot = state["bwd_buf"].at[S - 1].set(cot_last)
            B_cot = B_cot * valid_b[:, None, None, None].astype(B_cot.dtype)
            idx = jnp.mod(t - g_vec, R)
            X_b = _per_stage_gather(stash, idx)
            aux_cot = valid_b.astype(jnp.float32)

            if mode == "pipedream":
                stages_b = tuple(_ring_read(state["w_stash"][k], idx[k])
                                 for k in range(S))
            else:
                stages_b = stages
            stages_b = tuple(None if k in reuse else bwd_weights(t_)
                             for k, t_ in enumerate(stages_b))
            gW, gXs = [], []
            for k in range(S):
                with phases.stage(k):
                    vjp_k = kept_vjp.get(k)
                    if vjp_k is None:
                        _, vjp_k = jax.vjp(stage_fn, stages_b[k], X_b[k])
                    gw_k, gx_k = vjp_k((B_cot[k], aux_cot[k]))
                gW.append(gw_k)
                gXs.append(gx_k)
            gX = jnp.stack(gXs)

            # ---------- embed backward -------------------------------------
            old_batch = _ring_read(batch_ring, jnp.mod(t - lag_vec[0], R))
            _, evjp = jax.vjp(lambda o: model.embed(o, old_batch), outer)
            (g_outer_embed,) = evjp(gX[0] * valid_b[0].astype(gX.dtype))

        # ---------- per-tick, per-stage update ------------------------------
        with phases.scope("update"):
            g_outer = jax.tree.map(jnp.add, g_outer_head, g_outer_embed)
            grads = {"outer": g_outer, "stages": tuple(gW)}
            if clip:
                grads, _ = sgd.clip_by_global_norm(grads, clip)
            new_params, new_mom = sgd.update(
                params, sgd.MomentumState(mom), grads, lr=lr, gamma=gamma)
            new_pred = None
            if fused_predict:
                # Eq. 4 evaluated inside the update pass (the fused_update
                # kernel's schedule): for tick t+1, Ŵ = W_{t+1} − s·η·v_t.
                cdt = jnp.dtype(model.cfg.compute_dtype)
                new_pred = {
                    "stages": tuple(
                        jax.tree.map(lambda p: p.astype(cdt), t_)
                        for t_ in _predict_stages(new_params["stages"],
                                                  new_mom.v["stages"],
                                                  lr, s_fwd_v)),
                    "outer": jax.tree.map(
                        lambda p: p.astype(cdt),
                        st.predict_weights(new_params["outer"],
                                           new_mom.v["outer"], lr,
                                           s_fwd_embed)),
                }

        # ---------- rotate in-flight buffers --------------------------------
        with phases.scope("transfer"):
            A_next = jnp.roll(out, 1, axis=0)
            B_next = jnp.roll(gX, -1, axis=0)
            w_stash = None
            if mode == "pipedream":
                w_stash = tuple(
                    _ring_write(state["w_stash"][k], slot, stages[k])
                    for k in range(S))

        new_state = {
            **state,
            "params": new_params, "momentum": new_mom.v,
            "step": state["step"] + 1, "tick": t + 1,
            "fwd_buf": A_next, "bwd_buf": B_next,
            "stash_x": stash, "batch_ring": batch_ring,
        }
        if w_stash is not None:
            new_state["w_stash"] = w_stash
        if new_pred is not None:
            new_state["pred"] = new_pred
        return new_state, {"loss": loss, "loss_valid": valid_head}

    if ticks_per_step == 1:
        return tick_fn

    def train_step(state: Dict[str, Any], batch):
        T = ticks_per_step
        mbs = jax.tree.map(
            lambda x: x.reshape((T, x.shape[0] // T) + x.shape[1:]), batch)
        state, mets = jax.lax.scan(tick_fn, state, mbs)
        n = jnp.maximum(jnp.sum(mets["loss_valid"]), 1.0)
        return state, {"loss": jnp.sum(mets["loss"] * mets["loss_valid"]) / n,
                       "loss_valid": n}

    return train_step


# ===========================================================================
# IR-interpreter runtime: round-based schedules (gpipe / 1f1b / 2bw /
# interleaved) executed by walking the planner IR's event timeline
# ===========================================================================

# one source of truth lives next to the emitters (schedule_ir has no
# repro.core imports, so this does not cycle)
from repro.planner import schedule_ir as sir  # noqa: E402
from repro.planner.schedule_ir import ROUND_SCHEDULES as IR_SCHEDULES  # noqa: E402,E501

IR_BACKENDS = ("scan", "unrolled")
EXECS = ("spmd", "mpmd")


def _resolve_execution(execution, legacy, caller: str):
    """One-release back-compat shim for the old builtin-shadowing
    ``exec=`` keyword: resolve ``execution=`` (new) against a legacy
    ``**{"exec": ...}`` catch-all, warning on the old spelling and
    rejecting anything else that landed in the catch-all."""
    unknown = set(legacy) - {"exec"}
    if unknown:
        raise TypeError(f"{caller}() got unexpected keyword arguments "
                        f"{sorted(unknown)}")
    if "exec" in legacy:
        import warnings
        warnings.warn(
            f"{caller}(exec=...) is deprecated; pass execution= "
            f"instead (exec= will be removed next release)",
            DeprecationWarning, stacklevel=3)
        if execution is not None and execution != legacy["exec"]:
            raise TypeError(
                f"{caller}() got both execution={execution!r} and "
                f"legacy exec={legacy['exec']!r}")
        execution = legacy["exec"]
    execution = "spmd" if execution is None else execution
    if execution not in EXECS:
        raise ValueError(
            f"unknown execution {execution!r}; known: {EXECS}")
    return execution


def _mpmd_mesh(mesh, n_devices: int):
    """Resolve/validate the mesh the MPMD path shard_maps over: a
    ``pipe`` axis of exactly ``n_devices`` (one pipeline stage per
    device) and every other axis of size 1 — the path runs pure
    pipeline parallelism; data/tensor axes belong to the SPMD path."""
    from repro.runtime import sharding as rsh

    if mesh is None:
        mesh = rsh.mpmd_pipe_mesh(n_devices)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if sizes.get("pipe") != n_devices:
        raise ValueError(
            f"mpmd needs a mesh with a 'pipe' axis of size {n_devices} "
            f"(one device per pipeline stage), got axes {sizes}")
    extra = {k: v for k, v in sizes.items() if k != "pipe" and v != 1}
    if extra:
        raise ValueError(
            f"mpmd runs pure pipeline parallelism; non-pipe mesh axes "
            f"must have size 1, got {extra}")
    return mesh


def _unsupported(combo: str, why: str, use: str) -> NotImplementedError:
    """Structured ``NotImplementedError`` for unsupported feature
    combinations: names the combination, the reason it is out of scope,
    and the supported alternative — one fixed shape so every gate reads
    the same (tests pin all three parts)."""
    return NotImplementedError(
        f"unsupported combination: {combo} — {why}; "
        f"supported alternative: {use}")


def _ir_plan_check(model, plan) -> Tuple[int, ...]:
    """Validate a plan as an executable artifact for the IR interpreter;
    returns the per-chunk layer counts."""
    if plan is None:
        raise ValueError("the IR-interpreter runtime needs a plan "
                         "(repro.planner.plan(..., schedule='1f1b'|...))")
    if plan.schedule not in IR_SCHEDULES:
        raise ValueError(
            f"IR interpreter executes {IR_SCHEDULES}, got a "
            f"{plan.schedule!r} plan (the stream schedule runs through "
            f"make_train_step)")
    if plan.n_stages != model.n_stages:
        raise ValueError(f"plan has {plan.n_stages} device stages, model "
                         f"has {model.n_stages}")
    part = plan.partition
    if part.n_layers != model.cfg.n_layers:
        raise ValueError(f"plan partitions {part.n_layers} layers, model "
                         f"has {model.cfg.n_layers}")
    sizes = part.sizes()
    if len(sizes) != plan.n_chunks:
        raise ValueError(f"plan has {len(sizes)} chunk-stages, expected "
                         f"{plan.n_chunks} (= {plan.n_stages} stages × "
                         f"{plan.virtual_stages} virtual)")
    if min(sizes) < 1:
        raise ValueError(f"plan has an empty chunk-stage: sizes={sizes}")
    if plan.round_microbatches < 1:
        raise ValueError(f"plan carries no round size "
                         f"(round_microbatches={plan.round_microbatches})")
    depth = max(plan.w_stash_depth) if plan.w_stash_depth else 1
    if depth > 2:
        raise _unsupported(
            f"a {plan.schedule!r} plan with IR-derived weight-stash "
            f"depth {depth}",
            "the interpreter implements only single-buffer and 2BW "
            "double-buffer weight reads (depth <= 2)",
            "a schedule whose IR derives depth <= 2 (1f1b, gpipe, "
            "interleaved, 2bw)")
    return sizes


def _round_program(plan):
    """One canonical round of compute events, in timeline order.

    Each entry is ``(kind, local_mb, chunk_stage, s)`` with ``s`` the
    IR-derived version lag of that event's weight read (the per-(stage,
    microbatch) SpecTrain prediction distance).  Flush schedules use
    round 0; 2BW uses a steady accumulation group (every group executes
    identically under the double-buffer rotation) — the base selection
    and extraction live on the plan (``PipelinePlan.round_program``)."""
    return plan.round_program()


def make_ir_state(model, params, batch_sds, *, plan,
                  mode: str = "spectrain",
                  execution: Optional[str] = None,
                  mesh=None, verify: bool = True,
                  **legacy) -> Dict[str, Any]:
    """Train state for the IR interpreter: chunked params + momentum
    (+ the 2BW double buffer when the IR derives a stash depth of 2).

    ``params`` is the ragged canonical init layout (legacy stacked
    trees are accepted); its stage weights are repartitioned into
    ``plan.n_chunks`` ragged chunk trees by the plan's partition
    (virtual stages give a device several chunk trees —
    ``Model.device_chunk_params`` recovers the per-device grouping).
    Unlike the streaming runtime there are no activation rings: the
    interpreter's in-flight activations live inside one traced round,
    sized by the schedule itself (peak = ``plan.act_stash``).

    ``execution="mpmd"`` builds the packed stage-local layout instead: the
    ragged chunk trees are zero-padded and stacked into ``[v, S, Lmax,
    ...]`` leaves (``models.model.pack_chunk_params``) and device_put
    with ``P(None, 'pipe')`` on ``mesh`` (default: the first S local
    devices), so chunk ``q``'s weights/momentum/stash live *only* on
    pipe device ``q % S`` — per-device parameter memory drops to
    ~1/S.  The state additionally carries ``chunk_sizes`` (the ragged
    per-chunk layer counts, for unpacking/checkpoint migration).
    """
    assert mode in MODES, mode
    execution = _resolve_execution(execution, legacy, "make_ir_state")
    del batch_sds  # interpreter state holds no rings; shape-agnostic
    sizes = _ir_plan_check(model, plan)
    if verify:
        plan.verify()   # static artifact verification (planner/verify.py)
    chunks = model.partition_stage_params(params["stages"], sizes,
                                          n_chunks=plan.n_chunks)
    if execution == "mpmd":
        from repro.models.model import pack_chunk_params
        from repro.runtime import sharding as rsh

        if model.hybrid:
            raise _unsupported(
                "execution='mpmd' with a hybrid SSM/attention model",
                "per-stage 'shared' blocks have no flat layer order to "
                "pack into the [v, S, Lmax] stage-local layout",
                "execution='spmd' (runs hybrid models with every "
                "schedule)")
        mesh = _mpmd_mesh(mesh, plan.n_devices)
        packed, psizes = pack_chunk_params(chunks, plan.n_devices)
        assert psizes == tuple(sizes), (psizes, sizes)
        pparams = {"outer": params["outer"], "stages": packed}
        state: Dict[str, Any] = {
            "params": pparams,
            "momentum": sgd.init(pparams).v,
            "step": jnp.zeros((), jnp.int32),
            "chunk_sizes": jnp.asarray(sizes, jnp.int32),
        }
        if max(plan.w_stash_depth) > 1:
            state["stash"] = {
                "params": jax.tree.map(jnp.array, pparams),
                "momentum": jax.tree.map(jnp.array, state["momentum"]),
            }
        return jax.device_put(state, rsh.mpmd_state_shardings(mesh, state))
    params = {"outer": params["outer"], "stages": chunks}
    state = {
        "params": params,
        "momentum": sgd.init(params).v,
        "step": jnp.zeros((), jnp.int32),
    }
    if max(plan.w_stash_depth) > 1:
        # 2BW: reads are pinned one version back; stash starts equal to
        # params (version 0 reads version 0 — the IR's warm-up truncation)
        state["stash"] = {
            "params": jax.tree.map(jnp.array, params),
            "momentum": jax.tree.map(jnp.array, state["momentum"]),
        }
    return state


def make_ir_train_step(model, *, plan, mode: str = "spectrain", lr: float,
                       gamma: float = 0.9, clip: Optional[float] = None,
                       backend: str = "scan",
                       execution: Optional[str] = None, mesh=None,
                       verify: bool = True, **legacy) -> Callable:
    """Schedule-driven step: one call executes one flush round (gpipe /
    1f1b / interleaved) or one 2BW accumulation group of
    ``plan.round_microbatches`` microbatches, by interpreting the IR's
    compute events in timeline order.

    Weight reads per event:

      flush schedules   current weights — no update lands inside a round,
                        so every mode coincides (IR staleness 0)
      2bw               the stashed previous version (the double buffer);
                        ``spectrain`` predicts it forward by the event's
                        IR-derived lag (s = 1): Ŵ = W_prev − s·η·v_prev

    The gradient is the mean over the round's microbatches; the update
    applies once per round to current params (2BW then rotates the
    double buffer).

    ``backend`` selects how the round body is built:

      scan       (default) ``lax.scan`` over the plan's dense
                 :class:`~repro.planner.schedule_ir.EventTable`, one row
                 per compute event, dispatched by ``lax.switch`` over
                 the table's (opcode, chunk, lag) branches — trace size
                 O(#branches) ≤ 2·n_chunks, independent of M, so rounds
                 with M·C ≫ 100 compile in constant time
      unrolled   every compute event inlined into the trace (the
                 original interpreter) — O(M·C) trace, kept as the
                 reference oracle; ``tests/test_ir_scan.py`` pins the
                 scan backend bit-identical to it

    Both backends accumulate gradients, losses and the outer tree in
    the same timeline order, so they are bitwise interchangeable.

    Both bodies name their work with the phases of
    :mod:`repro.obs.phases` (metadata only).

    ``execution`` selects the execution model: ``"spmd"`` (default) runs the
    round as one replicated program (stage weights visible everywhere,
    GSPMD free to shard); ``"mpmd"`` runs each device's tick stream
    inside a ``shard_map`` over ``mesh``'s ``pipe`` axis against
    stage-*local* packed weights, moving activations/cotangents across
    the stage cuts via ``ppermute`` (see :func:`_make_mpmd_step`) —
    bitwise-identical losses and state leaves, ~1/S per-device weight
    memory.  ``backend`` applies to the SPMD path only; mpmd requires
    the matching ``make_ir_state(..., execution="mpmd")`` packed state and
    refuses ``clip`` and hybrid models.

    ``verify=True`` (the default) statically verifies the plan's
    compiled artifacts before building the step — slot dataflow, ring
    comm matching, closed-form staleness, completeness, exact resource
    bounds (``planner/verify.py``); ``verify=False`` skips it (the
    launcher's ``--no-verify``).
    """
    assert mode in MODES, mode
    if backend not in IR_BACKENDS:
        raise ValueError(
            f"unknown IR backend {backend!r}; known: {IR_BACKENDS}")
    execution = _resolve_execution(execution, legacy,
                                   "make_ir_train_step")
    if verify and plan is not None and plan.schedule in IR_SCHEDULES:
        plan.verify()   # static artifact verification (planner/verify.py)
    if execution == "mpmd":
        if clip:
            raise _unsupported(
                "execution='mpmd' with clip_by_global_norm",
                "the global norm's canonical-order reduction is not "
                "bit-reproducible on the packed stage layout",
                "execution='spmd' with clip, or execution='mpmd' with "
                "clip=None")
        if model.hybrid:
            raise _unsupported(
                "execution='mpmd' with a hybrid SSM/attention model",
                "per-stage 'shared' blocks have no flat layer order to "
                "pack into the [v, S, Lmax] stage-local layout",
                "execution='spmd' (runs hybrid models with every "
                "schedule)")
        return _make_mpmd_step(model, plan=plan, mode=mode, lr=lr,
                               gamma=gamma, mesh=mesh)
    sizes = _ir_plan_check(model, plan)
    del sizes
    prog = _round_program(plan)
    C = plan.n_chunks
    M = plan.round_microbatches
    two_buf = max(plan.w_stash_depth) > 1
    table = (sir.compile_event_table(prog, C, M) if backend == "scan"
             else None)

    def stage_fn(sp, xk):
        xk, aux = model.stage_apply(sp, (xk, jnp.zeros((), jnp.float32)))
        return xk, aux

    def step(state: Dict[str, Any], batch):
        B = jax.tree.leaves(batch)[0].shape[0]
        # ValueError, not assert: these invariants guard user-supplied
        # shapes and must survive `python -O`
        if B % M:
            raise ValueError(
                f"batch {B} not divisible by the {plan.schedule!r} plan's "
                f"round size (round_microbatches={M})")
        mbs = jax.tree.map(
            lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]), batch)
        mb = lambda m: jax.tree.map(lambda x: x[m], mbs)

        params, mom = state["params"], state["momentum"]
        if two_buf:
            base_p, base_m = state["stash"]["params"], \
                state["stash"]["momentum"]
        else:
            base_p, base_m = params, mom

        # per-(chunk, lag) read-weight cache: the IR drives prediction —
        # flush events carry s = 0 (no-op), 2BW events s = 1
        cache: Dict[Tuple[str, int], Any] = {}

        def chunk_w(q, s):
            key = ("c%d" % q, s)
            if key not in cache:
                w = base_p["stages"][q]
                if mode == "spectrain" and s > 0:
                    with phases.scope("predict"), phases.stage(q):
                        w = st.predict_weights(w, base_m["stages"][q], lr,
                                               float(s))
                cache[key] = w
            return cache[key]

        def outer_w(s):
            key = ("outer", s)
            if key not in cache:
                w = base_p["outer"]
                if mode == "spectrain" and s > 0:
                    with phases.scope("predict"):
                        w = st.predict_weights(w, base_m["outer"], lr,
                                               float(s))
                cache[key] = w
            return cache[key]

        # ------------------------------------------------ unrolled body
        def unrolled_round():
            acts: Dict[Tuple[int, int], Any] = {}  # (m, q) -> chunk input
            outs: Dict[Tuple[int, int], Any] = {}  # (m, q) -> chunk output
            cots: Dict[Tuple[int, int], Any] = {}  # (m, q) -> out cotangent
            g_chunks = [None] * C
            # the outer grad runs as two independent accumulators (head
            # contributions at chunk C-1, embed contributions at chunk
            # 0) combined once after the round — the association the
            # MPMD backend reproduces without per-event cross-device
            # traffic (head and embed live on different devices)
            g_out_h = g_out_e = None
            losses = []

            def acc(a, g):
                return g if a is None else jax.tree.map(jnp.add, a, g)

            for kind, m, q, s in prog:
                if kind == "fwd":
                    with phases.scope("forward"), phases.stage(q):
                        x = model.embed(outer_w(s), mb(m)) if q == 0 \
                            else outs.pop((m, q - 1))
                        acts[(m, q)] = x
                        out, _aux = stage_fn(chunk_w(q, s), x)
                        outs[(m, q)] = out
                    continue
                if q == C - 1:
                    with phases.scope("head"):
                        tgt = mb(m)["targets"]
                        loss_m, head_vjp = jax.vjp(
                            lambda o, xl: model.head_loss(o, xl, tgt),
                            outer_w(s), outs.pop((m, q)))
                        go_head, cot = head_vjp(jnp.ones((), loss_m.dtype))
                        g_out_h = acc(g_out_h, go_head)
                        losses.append(loss_m)
                else:
                    cot = cots.pop((m, q + 1))
                with phases.scope("backward"), phases.stage(q):
                    _, vjp_q = jax.vjp(stage_fn, chunk_w(q, s),
                                       acts.pop((m, q)))
                    gw, gx = vjp_q((cot, jnp.ones((), jnp.float32)))
                    g_chunks[q] = acc(g_chunks[q], gw)
                    if q == 0:
                        _, evjp = jax.vjp(
                            lambda o: model.embed(o, mb(m)), outer_w(s))
                        (go_embed,) = evjp(gx)
                        g_out_e = acc(g_out_e, go_embed)
                    else:
                        cots[(m, q)] = gx
            if acts or outs or cots:
                raise ValueError(
                    f"{plan.schedule!r} round program (round size {M}) "
                    f"left in-flight tensors: "
                    f"{sorted(acts) + sorted(outs) + sorted(cots)}")
            g_outer = jax.tree.map(jnp.add, g_out_h, g_out_e)
            return g_outer, tuple(g_chunks), sum(losses) / len(losses)

        # ---------------------------------------------------- scan body
        def scan_round():
            # activation/cotangent pools: uniform [n_slots, mb, seq, d]
            # rings indexed by the table's register-allocated slots
            # (d_model is constant at every cut, so one buffer serves
            # all chunks; weights stay ragged per-chunk trees)
            as_sds = lambda t: jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
            mb_sds = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), mbs)
            x_sd = jax.eval_shape(model.embed, as_sds(base_p["outer"]),
                                  mb_sds)
            out_sd, _ = jax.eval_shape(stage_fn,
                                       as_sds(base_p["stages"][0]), x_sd)
            if (out_sd.shape, out_sd.dtype) != (x_sd.shape, x_sd.dtype):
                raise ValueError(
                    f"scan backend needs one uniform activation pool, got "
                    f"embed {x_sd.shape}/{x_sd.dtype} vs stage "
                    f"{out_sd.shape}/{out_sd.dtype}")
            loss_sd = jax.eval_shape(model.head_loss,
                                     as_sds(base_p["outer"]), out_sd,
                                     mb_sds["targets"])

            def first_or_add(acc, g, first):
                # bit-compat with the unrolled body's None-then-assign
                # accumulator: the first contribution must be g itself,
                # not 0 + g (which flips the sign bit of exact -0.0s)
                return jax.tree.map(
                    lambda a, gg: jnp.where(first, gg, a + gg), acc, g)

            def fwd_branch(q, s):
                W, Wo = chunk_w(q, s), outer_w(s)

                def br(carry, row):
                    P, Q, gs, goh, goe, ls = carry
                    m = row[sir.COL_MB]
                    if q == 0:
                        with phases.scope("forward"), phases.stage(q):
                            x = model.embed(Wo, mb(m))
                        with phases.scope("transfer"):
                            P = jax.lax.dynamic_update_index_in_dim(
                                P, x, row[sir.COL_A], 0)
                    else:
                        with phases.scope("forward"), phases.stage(q):
                            x = jax.lax.dynamic_index_in_dim(
                                P, row[sir.COL_A], 0, keepdims=False)
                    with phases.scope("forward"), phases.stage(q):
                        out, _aux = stage_fn(W, x)
                    with phases.scope("transfer"):
                        P = jax.lax.dynamic_update_index_in_dim(
                            P, out, row[sir.COL_B], 0)
                    return (P, Q, gs, goh, goe, ls)
                return br

            def bwd_branch(q, s):
                W, Wo = chunk_w(q, s), outer_w(s)

                def br(carry, row):
                    P, Q, gs, goh, goe, ls = carry
                    first_g = row[sir.COL_FIRST_G] > 0
                    m = row[sir.COL_MB]
                    with phases.scope("backward"), phases.stage(q):
                        x = jax.lax.dynamic_index_in_dim(
                            P, row[sir.COL_A], 0, keepdims=False)
                    if q == C - 1:
                        with phases.scope("head"):
                            out = jax.lax.dynamic_index_in_dim(
                                P, row[sir.COL_B], 0, keepdims=False)
                            tgt = mb(m)["targets"]
                            loss_m, head_vjp = jax.vjp(
                                lambda o, xl: model.head_loss(o, xl, tgt),
                                Wo, out)
                            go_head, cot = head_vjp(
                                jnp.ones((), loss_m.dtype))
                            goh = first_or_add(goh, go_head,
                                               row[sir.COL_FIRST_O] > 0)
                            ls = ls + loss_m
                    with phases.scope("backward"), phases.stage(q):
                        if q != C - 1:
                            cot = jax.lax.dynamic_index_in_dim(
                                Q, row[sir.COL_B], 0, keepdims=False)
                        _, vjp_q = jax.vjp(stage_fn, W, x)
                        gw, gx = vjp_q((cot, jnp.ones((), jnp.float32)))
                        gs = tuple(
                            first_or_add(t, gw, first_g) if i == q else t
                            for i, t in enumerate(gs))
                        if q == 0:
                            _, evjp = jax.vjp(
                                lambda o: model.embed(o, mb(m)), Wo)
                            (go_embed,) = evjp(gx)
                            goe = first_or_add(goe, go_embed,
                                               row[sir.COL_FIRST_E] > 0)
                    if q != 0:
                        with phases.scope("transfer"):
                            Q = jax.lax.dynamic_update_index_in_dim(
                                Q, gx, row[sir.COL_C], 0)
                    return (P, Q, gs, goh, goe, ls)
                return br

            branches = [fwd_branch(q, s) if kind == "fwd"
                        else bwd_branch(q, s)
                        for kind, q, s in table.branches]

            def body(carry, row):
                carry = jax.lax.switch(row[sir.COL_BRANCH], branches,
                                       carry, row)
                return carry, None

            carry0 = (
                jnp.zeros((table.n_val_slots,) + x_sd.shape, x_sd.dtype),
                jnp.zeros((max(table.n_cot_slots, 1),) + x_sd.shape,
                          x_sd.dtype),
                jax.tree.map(jnp.zeros_like, params["stages"]),
                jax.tree.map(jnp.zeros_like, params["outer"]),
                jax.tree.map(jnp.zeros_like, params["outer"]),
                jnp.zeros((), loss_sd.dtype),
            )
            (_, _, g_chunks, go_h, go_e, loss_sum), _ = jax.lax.scan(
                body, carry0, jnp.asarray(table.rows))
            g_outer = jax.tree.map(jnp.add, go_h, go_e)
            return g_outer, g_chunks, loss_sum / M

        g_outer, g_chunks, loss = (scan_round if backend == "scan"
                                   else unrolled_round)()
        with phases.scope("update"):
            grads = {"outer": g_outer, "stages": tuple(g_chunks)}
            grads = jax.tree.map(lambda g: g / M, grads)
            if clip:
                grads, _ = sgd.clip_by_global_norm(grads, clip)
            new_params, new_mom = sgd.update(
                params, sgd.MomentumState(mom), grads, lr=lr, gamma=gamma)
        new_state = {
            **state,
            "params": new_params, "momentum": new_mom.v,
            "step": state["step"] + 1,
        }
        if two_buf:
            new_state["stash"] = {"params": params, "momentum": mom}
        return new_state, {"loss": loss,
                           "loss_valid": jnp.ones((), jnp.float32)}

    return step


# ===========================================================================
# MPMD execution path: stage-local weights via shard_map, activations
# and cotangents crossing the stage cuts via ppermute ring transfers
# ===========================================================================

def _make_mpmd_step(model, *, plan, mode, lr, gamma, mesh):
    """True MPMD round body: one ``shard_map`` over the ``pipe`` axis
    runs each device's tick stream (:meth:`PipelinePlan.device_streams`)
    against its *local* packed weight shard.

    Per tick every device (1) ``lax.switch``-dispatches its row's branch
    — a (kind, chunk, lag) compute event or the NOP — reading/writing
    its private activation/cotangent slot pools and statically slicing
    its own chunks out of the packed ``[v, 1, Lmax, ...]`` shard, then
    (2) the whole mesh runs two ``ppermute`` rings (forward ring
    ``d -> d+1`` carries the tick's stage outputs, backward ring
    ``d -> d-1`` the cotangents) and (3) parks the received payload in
    the slot its row names (or a trash slot on idle ticks, so the
    program stays SPMD-uniform while the *execution* is MPMD: different
    devices run different branches each tick).

    Bitwise parity with the SPMD interpreters is by construction, not
    tolerance: a device's stream preserves the global timeline order of
    its own chunks' events, so every per-chunk gradient accumulates in
    scan order; the outer gradient runs as the same two head/embed
    accumulators the SPMD bodies use (head contributions live on device
    ``(C-1) % S``, embed on device 0) combined once outside the
    shard_map by *static indexing* of the per-device partials — no
    psum, whose identity-element adds would flip -0.0 bits.  The update
    itself is elementwise on the packed layout (padding rows stay
    exactly zero), so unpacking the new state reproduces the SPMD state
    leaves byte-for-byte.
    """
    from jax.sharding import PartitionSpec as P

    sizes = _ir_plan_check(model, plan)
    streams = plan.device_streams()
    C, M, S = plan.n_chunks, plan.round_microbatches, plan.n_devices
    two_buf = max(plan.w_stash_depth) > 1
    mesh = _mpmd_mesh(mesh, S)
    d_head = (C - 1) % S
    # the outer gradient accumulates per device: head contributions on
    # d_head, embed ones on device 0.  With S > 1 these differ, so one
    # outer-sized accumulator per device holds whichever it takes; with
    # S == 1 the two stay apart so the sums keep the SPMD bodies' order
    n_outer = 2 if d_head == 0 else 1
    i_head, i_embed = 0, n_outer - 1
    nv, nc = streams.n_val_slots, streams.n_cot_slots
    lags = sorted({s for _k, _q, s in streams.branches})
    rows = jnp.asarray(streams.rows)          # [T, S, DN_COLS]
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    def stage_fn(sp, xk):
        xk, aux = model.stage_apply(sp, (xk, jnp.zeros((), jnp.float32)))
        return xk, aux

    def _pre(state, batch):
        """Round prologue: microbatch split + per-lag weight reads.

        Prediction (Eq. 4) is elementwise, so predicting the whole
        packed tree equals the SPMD per-chunk prediction bit-for-bit
        (padding stays zero).  One read per distinct lag, *outside*
        the shard_map."""
        mbs = jax.tree.map(
            lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]), batch)
        if two_buf:
            base_p, base_m = state["stash"]["params"], \
                state["stash"]["momentum"]
        else:
            base_p, base_m = state["params"], state["momentum"]
        stage_rd = {}
        outer_rd = {}
        for s in lags:
            if mode == "spectrain" and s > 0:
                with phases.scope("predict"):
                    stage_rd[s] = st.predict_weights(
                        base_p["stages"], base_m["stages"], lr, float(s))
                    outer_rd[s] = st.predict_weights(
                        base_p["outer"], base_m["outer"], lr, float(s))
            else:
                stage_rd[s] = base_p["stages"]
                outer_rd[s] = base_p["outer"]
        return mbs, stage_rd, outer_rd

    def _post(state, gs_g, go_g, ls_g):
        """Round epilogue: combine the per-device outer partials by
        *static indexing* (head lives on device (C-1)%S, embed on
        device 0) — the one cross-device add of the round, in the same
        head+embed order as the SPMD bodies (a psum would add identity
        elements and flip -0.0 bits) — then apply the SGD update."""
        params, mom = state["params"], state["momentum"]
        loss = ls_g[d_head] / M
        with phases.scope("update"):
            go = jax.tree.map(lambda h, e: h[d_head] + e[0], go_g[i_head],
                              go_g[i_embed])
            grads = {"outer": go, "stages": gs_g}
            grads = jax.tree.map(lambda g: g / M, grads)
            new_params, new_mom = sgd.update(
                params, sgd.MomentumState(mom), grads, lr=lr, gamma=gamma)
        new_state = {
            **state,
            "params": new_params, "momentum": new_mom.v,
            "step": state["step"] + 1,
        }
        if two_buf:
            new_state["stash"] = {"params": params, "momentum": mom}
        return new_state, {"loss": loss,
                           "loss_valid": jnp.ones((), jnp.float32)}

    def step(state: Dict[str, Any], batch):
        B = jax.tree.leaves(batch)[0].shape[0]
        if B % M:
            raise ValueError(
                f"batch {B} not divisible by the {plan.schedule!r} plan's "
                f"round size (round_microbatches={M})")
        base_p = state["stash"]["params"] if two_buf \
            else state["params"]

        as_sds = lambda t: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
        mb_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (x.shape[0] // M,) + x.shape[1:], x.dtype), batch)
        x_sd = jax.eval_shape(model.embed, as_sds(base_p["outer"]), mb_sds)
        chunk0_sds = {"layers": jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((sizes[0],) + a.shape[3:],
                                           a.dtype),
            base_p["stages"]["layers"])}
        out_sd, _ = jax.eval_shape(stage_fn, chunk0_sds, x_sd)
        if (out_sd.shape, out_sd.dtype) != (x_sd.shape, x_sd.dtype):
            raise ValueError(
                f"mpmd needs one uniform activation/transfer shape, got "
                f"embed {x_sd.shape}/{x_sd.dtype} vs stage "
                f"{out_sd.shape}/{out_sd.dtype}")
        loss_sd = jax.eval_shape(model.head_loss, as_sds(base_p["outer"]),
                                 out_sd, mb_sds["targets"])
        zeros_x = lambda: jnp.zeros(x_sd.shape, x_sd.dtype)

        def make_tick(mbs_l, srd_l, ord_l):
            """The shared per-tick body, closed over a device's *local*
            views: replicated microbatches, the packed weight shard
            ``[v, 1, Lmax, ...]`` per lag, the replicated outer reads."""
            mb = lambda m: jax.tree.map(lambda x: x[m], mbs_l)

            def chunk_of(s, q):
                j, Lq = q // S, sizes[q]
                return {"layers": jax.tree.map(
                    lambda a: a[j, 0, :Lq], srd_l[s]["layers"])}

            def first_or_add(acc, g, first):
                return jax.tree.map(
                    lambda a, gg: jnp.where(first, gg, a + gg), acc, g)

            def outer_acc(go, i, g, first):
                return go[:i] + (first_or_add(go[i], g, first),) + go[i + 1:]

            def gs_acc(gs, gw, q, first):
                # static in-place accumulate of chunk q's ragged grad
                # into the packed local shard (padding rows untouched)
                j, Lq = q // S, sizes[q]

                def leaf(a, g):
                    cur = a[j, 0, :Lq]
                    return a.at[j, 0, :Lq].set(jnp.where(first, g, cur + g))

                return {"layers": jax.tree.map(leaf, gs["layers"],
                                               gw["layers"])}

            def mk_fwd(q, s):
                def br(carry, row):
                    V, Ct, gs, go, ls = carry
                    m = row[sir.DCOL_MB]
                    if q == 0:
                        with phases.scope("forward"), phases.stage(q):
                            x = model.embed(ord_l[s], mb(m))
                        with phases.scope("transfer"):
                            V = jax.lax.dynamic_update_index_in_dim(
                                V, x, row[sir.DCOL_A], 0)
                    else:
                        with phases.scope("forward"), phases.stage(q):
                            x = jax.lax.dynamic_index_in_dim(
                                V, row[sir.DCOL_A], 0, keepdims=False)
                    with phases.scope("forward"), phases.stage(q):
                        out, _aux = stage_fn(chunk_of(s, q), x)
                    with phases.scope("transfer"):
                        if q == C - 1:
                            V = jax.lax.dynamic_update_index_in_dim(
                                V, out, row[sir.DCOL_B], 0)
                            sf = zeros_x()
                        else:
                            sf = out
                    return (V, Ct, gs, go, ls), sf, zeros_x()
                return br

            def mk_bwd(q, s):
                def br(carry, row):
                    V, Ct, gs, go, ls = carry
                    m = row[sir.DCOL_MB]
                    with phases.scope("backward"), phases.stage(q):
                        x = jax.lax.dynamic_index_in_dim(
                            V, row[sir.DCOL_A], 0, keepdims=False)
                    if q == C - 1:
                        with phases.scope("head"):
                            out = jax.lax.dynamic_index_in_dim(
                                V, row[sir.DCOL_B], 0, keepdims=False)
                            tgt = mb(m)["targets"]
                            loss_m, head_vjp = jax.vjp(
                                lambda o, xl: model.head_loss(o, xl, tgt),
                                ord_l[s], out)
                            go_head, cot = head_vjp(
                                jnp.ones((), loss_m.dtype))
                            go = outer_acc(go, i_head, go_head,
                                           row[sir.DCOL_FIRST_O] > 0)
                            ls = ls + loss_m
                    with phases.scope("backward"), phases.stage(q):
                        if q != C - 1:
                            cot = jax.lax.dynamic_index_in_dim(
                                Ct, row[sir.DCOL_C], 0, keepdims=False)
                        _, vjp_q = jax.vjp(stage_fn, chunk_of(s, q), x)
                        gw, gx = vjp_q((cot, jnp.ones((), jnp.float32)))
                        gs = gs_acc(gs, gw, q, row[sir.DCOL_FIRST_G] > 0)
                        if q == 0:
                            _, evjp = jax.vjp(
                                lambda o: model.embed(o, mb(m)), ord_l[s])
                            (go_embed,) = evjp(gx)
                            go = outer_acc(go, i_embed, go_embed,
                                           row[sir.DCOL_FIRST_E] > 0)
                    sb = zeros_x() if q == 0 else gx
                    return (V, Ct, gs, go, ls), zeros_x(), sb
                return br

            branches = [mk_fwd(q, s) if kind == "fwd" else mk_bwd(q, s)
                        for kind, q, s in streams.branches]
            branches.append(
                lambda carry, row: (carry, zeros_x(), zeros_x()))

            def tick(carry, row):
                carry, sf, sb = jax.lax.switch(
                    row[sir.DCOL_BRANCH], branches, carry, row)
                # both rings run every tick (idle devices carry the
                # NOP's garbage payload into a trash slot) so the
                # program stays SPMD while the execution is MPMD
                with phases.scope("transfer"):
                    rf = jax.lax.ppermute(sf, "pipe", fwd_perm) if S > 1 \
                        else sf
                    rb = jax.lax.ppermute(sb, "pipe", bwd_perm) if S > 1 \
                        else sb
                    V, Ct, gs, go, ls = carry
                    V = jax.lax.dynamic_update_index_in_dim(
                        V, rf, jnp.where(row[sir.DCOL_RECV_F] >= 0,
                                         row[sir.DCOL_RECV_F], nv), 0)
                    Ct = jax.lax.dynamic_update_index_in_dim(
                        Ct, rb, jnp.where(row[sir.DCOL_RECV_B] >= 0,
                                          row[sir.DCOL_RECV_B], nc), 0)
                return (V, Ct, gs, go, ls)
            return tick

        def local_carry0(srd_l, ord_l):
            return (
                jnp.zeros((nv + 1,) + x_sd.shape, x_sd.dtype),
                jnp.zeros((nc + 1,) + x_sd.shape, x_sd.dtype),
                jax.tree.map(jnp.zeros_like, srd_l[lags[0]]),
                tuple(jax.tree.map(jnp.zeros_like, ord_l[lags[0]])
                      for _ in range(n_outer)),
                jnp.zeros((), loss_sd.dtype),
            )

        expand = lambda t: jax.tree.map(lambda x: x[None], t)

        mbs, stage_rd, outer_rd = _pre(state, batch)

        def round_body(rows_l, mbs_l, srd_l, ord_l):
            tick = make_tick(mbs_l, srd_l, ord_l)

            def body(carry, row):
                return tick(carry, row[0]), None

            (_V, _Ct, gs, go, ls), _ = jax.lax.scan(
                body, local_carry0(srd_l, ord_l), rows_l)
            return gs, expand(go), ls[None]

        run = jax.shard_map(
            round_body, mesh=mesh,
            in_specs=(P(None, "pipe", None), P(), P(None, "pipe"), P()),
            out_specs=(P(None, "pipe"), P("pipe"), P("pipe")),
            check_vma=False)
        gs_g, go_g, ls_g = run(rows, mbs, stage_rd, outer_rd)
        return _post(state, gs_g, go_g, ls_g)

    return step
