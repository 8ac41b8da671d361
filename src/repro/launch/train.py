"""End-to-end training driver.

CPU-scale runs use reduced configs (``--smoke``) or an explicit size
override; the same code path drives the production mesh on real hardware.
Supports all four schemes (sync / vanilla / pipedream / spectrain),
checkpoint/restart (``--resume auto``), gradient compression, fault
injection, and exact-resume determinism.

Example (the 8-deliverable end-to-end run):
    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
        --pipe 2 --layers 4 --steps 100 --lr 2e-2 --mode spectrain

``--schedule {stream,gpipe,1f1b,2bw,interleaved}`` selects the pipeline
schedule (round schedules run through the IR interpreter, one flush
round / 2BW group per step); ``--virtual-stages v`` gives each device v
chunk-stages under ``--schedule interleaved``; ``--ir-backend
{scan,unrolled}`` picks the interpreter's round body (the default scan
backend keeps trace size O(1) in the round's microbatch count);
``--execution {spmd,mpmd}`` picks the execution backend (``mpmd``
keeps each stage's weights resident only on its pipe device — bitwise
the same training, 1/S the per-device weight memory; ``--exec`` is the
deprecated alias).  See docs/SCHEDULES.md.

The execution knobs flow through one ``repro.api.RuntimeConfig``
(built by ``repro.api.runtime_config_from_args``, the wiring shared
with ``launch/serve.py``) and the steps through the ``repro.api.
Runtime`` facade.

``--layers`` need not divide ``--pipe``: stage params are ragged
per-stage trees (e.g. ``--layers 7 --pipe 3`` runs sizes (3,2,2) under
the default partitioner, or whatever split ``--partitioner dp``
computes), and checkpoints written by any partition restore onto any
other via the flat layer order.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import time
from typing import Any, Dict, NamedTuple

import jax
import numpy as np

from repro.api import (Runtime, RuntimeConfig, add_runtime_args,
                       runtime_config_from_args)
from repro.configs import get_config, smoke_config
from repro.core import pipeline_stream, pipeline_sync
from repro.data import KINDS, DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.obs import MetricsRegistry, format_step
from repro.planner import check_against_closed_forms, plan as make_plan
from repro.runtime import checkpoint as ckpt


def build(args):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    kw = {}
    if args.layers:
        kw["n_layers"] = args.layers
    if args.d_model:
        kw["d_model"] = args.d_model
        kw["head_dim"] = max(8, args.d_model // cfg.n_heads)
        kw["d_ff"] = args.d_model * 4
    if args.vocab:
        kw["vocab_size"] = args.vocab
    kw["mesh_plan"] = dataclasses.replace(
        cfg.mesh_plan, pipe=args.pipe, tensor=1,
        num_microbatches=args.ticks)
    kw["param_dtype"] = "float32"
    kw["compute_dtype"] = args.dtype
    cfg = cfg.replace(**kw)
    return cfg


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0, dest="d_model")
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--pipe", type=int, default=2)
    ap.add_argument("--ticks", type=int, default=1)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--data", default="bigram", choices=KINDS,
                    help="synthetic token stream: 'bigram' (learnable, "
                         "V x V table, small vocabularies only) or "
                         "'uniform' (i.i.d. tokens, any vocabulary)")
    add_runtime_args(ap)
    ap.add_argument("--virtual-stages", type=int, default=1,
                    dest="virtual_stages",
                    help="chunks per device for --schedule interleaved "
                         "(v >= 2 shrinks the flush bubble ~v x)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--resume", default="", choices=("", "auto"))
    ap.add_argument("--compress", default="", choices=("", "topk", "int8"))
    ap.add_argument("--partitioner", default="dp", choices=("dp", "uniform"),
                    help="stage-partition method for the planner")
    ap.add_argument("--profile-method", default="analytic",
                    choices=("auto", "hlo", "timed", "analytic"),
                    dest="profile_method",
                    help="per-layer cost acquisition for the planner")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line per logged step")
    ap.add_argument("--trace", default="", metavar="DIR",
                    help="profile every step after the first (compiling) "
                         "one with jax.profiler into DIR: an .xplane.pb "
                         "for XProf and a Perfetto trace, each operation "
                         "named by its step phase (repro.obs.phases)")
    ap.add_argument("--metrics-out", default="", dest="metrics_out",
                    help="append structured JSONL telemetry (step records, "
                         "heartbeat/restate events, summary) to this path")
    return ap.parse_args(argv)


class Setup(NamedTuple):
    """What a run builds before it creates any state."""
    rc: RuntimeConfig
    cfg: Any
    model: Model
    data: SyntheticLM
    batch_sds: Dict[str, jax.ShapeDtypeStruct]
    plan: Any
    schedule: str


def setup(args) -> Setup:
    """Config, model, data stream, plan and RuntimeConfig for parsed
    flags; prints the ``# plan`` lines.  Shared by :func:`main` and
    the chip smoke run, which compiles the same step programs."""
    try:
        rc = runtime_config_from_args(args,
                                      ticks_per_step=max(args.ticks, 1))
    except ValueError as e:
        raise SystemExit(str(e))

    cfg = build(args)
    model = Model(cfg)
    try:
        data = SyntheticLM(DataConfig(cfg.vocab_size, args.seq, args.batch,
                                      seed=args.seed, kind=args.data))
    except ValueError as e:
        raise SystemExit(str(e))
    batch_sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), data.batch_at(0))

    # profile-guided plan: partition + IR-derived staleness for the
    # schedule this run executes (gpipe for the sync fill/drain pipeline,
    # --schedule otherwise).  The partition is executed: the runtimes
    # regroup stage weights into ragged per-(chunk-)stage trees by its
    # layer ranges, so --partitioner dp changes which layers each stage
    # runs, not just the printed bottleneck.
    if args.mode == "sync" and args.schedule != "stream":
        raise SystemExit(
            f"--mode sync runs the fill/drain pipeline and cannot honor "
            f"--schedule {args.schedule}; drop one of the two flags")
    if args.virtual_stages > 1 and args.schedule != "interleaved":
        raise SystemExit(
            f"--virtual-stages {args.virtual_stages} requires "
            f"--schedule interleaved, got --schedule {args.schedule}")
    if rc.execution == "mpmd" and args.mode == "sync":
        raise SystemExit(
            f"--execution mpmd runs IR round schedules "
            f"({'/'.join(pipeline_stream.IR_SCHEDULES)}); got "
            f"--mode sync")
    schedule = "gpipe" if args.mode == "sync" else args.schedule
    plan_kw = {}
    if schedule in pipeline_stream.IR_SCHEDULES and args.mode != "sync":
        # round size: --ticks when given, else the largest batch divisor
        # compatible with the schedule (interleaved groups microbatches
        # by S, 2bw needs m >= S for its two weight buffers); the
        # interpreter splits the global batch into the round's
        # microbatches, so M must divide the batch
        S, v = args.pipe, args.virtual_stages

        def legal(M):
            if args.batch % M:
                return False
            if schedule == "interleaved":
                return M % S == 0
            if schedule == "2bw":
                return M >= S
            return True

        M = args.ticks if args.ticks > 1 else next(
            (c for c in range(min(2 * S * v, args.batch), 0, -1)
             if legal(c)), 0)
        if not M or not legal(M):
            raise SystemExit(
                f"no round size for --schedule {schedule}: need a "
                f"divisor of --batch {args.batch} that is "
                + ('a multiple of' if schedule == 'interleaved'
                   else 'at least') + " "
                f"--pipe {S}" + (f" (got --ticks {M})" if M else ""))
        plan_kw["n_microbatches"] = M
    pplan = make_plan(
        cfg, n_stages=model.n_stages, schedule=schedule,
        virtual_stages=args.virtual_stages,
        partitioner=args.partitioner, profile_method=args.profile_method,
        batch=args.batch, seq=args.seq, **plan_kw)
    check_against_closed_forms(pplan)
    prof = pplan.profile
    fell_back = f" ({prof.fallback})" if prof.fallback else ""
    print(f"# {pplan.summary()} profile={prof.method}{fell_back}")
    stage_desc = " ".join(
        f"s{k}:L[{lo}:{hi})={c:.2e}s"
        for k, ((lo, hi), c) in enumerate(zip(pplan.stage_ranges,
                                              pplan.stage_costs_s)))
    print(f"# realized stages: {stage_desc}  "
          f"bottleneck={pplan.bottleneck_s:.2e}s "
          f"(uniform would be {pplan.uniform_bottleneck_s:.2e}s)")
    if schedule in pipeline_stream.IR_SCHEDULES and args.mode != "sync":
        print(f"# schedule {schedule}: round={pplan.round_microbatches} "
              f"microbatches, bubble={pplan.bubble_frac:.3f}, "
              f"act_stash={pplan.act_stash}, "
              f"w_stash_depth={pplan.w_stash_depth}")
    if schedule == "stream" and args.mode != "sync":
        reuse = pipeline_stream.forward_reuse_stages(
            model.n_stages, args.mode, pplan)
        print(f"# stream: backward reuses the forward of stages {reuse}")
    return Setup(rc, cfg, model, data, batch_sds, pplan, schedule)


def main(argv=None) -> int:
    enable_compile_cache()
    args = parse_args(argv)
    rc, cfg, model, data, batch_sds, pplan, schedule = setup(args)
    key = jax.random.PRNGKey(args.seed)

    registry = MetricsRegistry(jsonl_path=args.metrics_out or None)

    if args.mode == "sync":
        state = pipeline_sync.init_state(model, key)
        step_fn = pipeline_sync.make_train_step(
            model, lr=args.lr, gamma=args.gamma,
            num_microbatches=cfg.mesh_plan.num_microbatches,
            clip=args.clip or None)
        step_fn = jax.jit(step_fn, donate_argnums=0)
    else:
        # the Runtime facade owns jit/donation for both schedule families
        rt = Runtime(pplan, model, rc)
        state = rt.init(key, batch_sds)
        step_fn = rt.train_step

    start = 0
    if args.resume == "auto" and args.ckpt_dir:
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            state, last = ckpt.restore(args.ckpt_dir, state)
            start = last + 1
            print(f"# resumed from step {last}")

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(state["params"]))
    print(f"# arch={cfg.name} params={n_params:,} mode={args.mode} "
          f"pipe={model.n_stages} opt_floor={data.optimal_loss():.4f}")

    # tok/s counts from the end of the first step, which compiles
    t0 = time.time()
    tokens = 0
    bg_save = None
    interrupted = False
    profile = contextlib.ExitStack()
    try:
        for s in range(start, args.steps):
            if args.trace and s == start + 1:
                profile.enter_context(jax.profiler.trace(
                    args.trace, create_perfetto_trace=True))
            batch = data.batch_at(s)
            with jax.profiler.StepTraceAnnotation("train_step",
                                                  step_num=s + 1):
                state, metrics = step_fn(state, batch)
            if s == start:
                jax.block_until_ready(metrics["loss"])
                t0 = time.time()
            else:
                tokens += args.batch * args.seq
            if args.ckpt_dir and (s + 1) % args.save_every == 0:
                if bg_save is not None:
                    bg_save.join()  # never two writers on the same dir
                bg_save = ckpt.save(args.ckpt_dir, state, s,
                                    background=True)
            if (s + 1) % args.log_every == 0 or s == args.steps - 1:
                loss = float(metrics["loss"])
                dt = time.time() - t0
                rec = registry.log_step(
                    step=s + 1, loss=round(loss, 4),
                    tok_per_s=round(tokens / max(dt, 1e-9), 1))
                print(json.dumps(rec) if args.json else format_step(rec))
    except KeyboardInterrupt:
        interrupted = True
        print("# interrupted -- metrics flushed")
    finally:
        profile.close()
        registry.close()
    if args.ckpt_dir:
        if bg_save is not None:
            bg_save.join()
        if not interrupted:
            ckpt.save(args.ckpt_dir, state, args.steps - 1)
    if args.trace and args.steps - start > 1:
        print(f"# profile of steps {start + 2}..{args.steps} written to "
              f"{args.trace}")
    return 1 if interrupted else 0


if __name__ == "__main__":
    raise SystemExit(main())
