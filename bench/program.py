"""The system under test, built as ``repro.launch.train`` builds a run.

``build`` turns a cell's configuration and traffic files into the
launcher's flags, calls ``train.setup`` (plan, model, data stream,
``RuntimeConfig``) and binds ``repro.api.Runtime``.  ``Runtime.init``
then makes the state on the device, with the model's ``init`` pointed
at the benchmark's own weights (``bench.weights``), so that the
reference can draw the same weights again without taking them from the
program.  ``check`` drives that same runtime through its first steps
with the window's own call (``Runtime.train_step``) and feed
(``SyntheticLM.batch_at``), and reads what the comparison needs.
"""
from __future__ import annotations

import contextlib
import functools
import io
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as wt
from bench.reference.train import check_steps, warmup


def train_argv(cfg: dict, job: dict, seed: int):
    return ["--arch", cfg["repo_arch"],
            "--layers", str(job["stages"] * job["layers_per_stage"]),
            "--pipe", str(job["stages"]),
            "--dtype", cfg["compute_dtype"],
            "--data", job["data"],
            "--batch", str(job["batch"]), "--seq", str(job["seq"]),
            "--seed", str(seed),
            "--schedule", job["schedule"], "--mode", job["mode"],
            "--execution", job["execution"],
            "--ticks", str(job["microbatches"]),
            "--partitioner", job["partitioner"],
            "--lr", repr(job["lr"]), "--gamma", repr(job["gamma"])]


class Program(NamedTuple):
    run: Any            # repro.launch.train.Setup
    runtime: Any        # repro.api.Runtime
    step: Callable      # the timed call: (state, batch) -> (state, metrics)
    plan_lines: str     # what train.setup printed


def build(cfg: dict, job: dict, seed: int, *, smoke: bool = False,
          step_wrapper: Optional[Callable] = None) -> Program:
    """``smoke`` runs the registry's smoke widths (CPU tests);
    ``step_wrapper`` wraps the timed call (the fault tests)."""
    from repro.api import Runtime
    from repro.launch import train

    argv = train_argv(cfg, job, seed) + (["--smoke"] if smoke else [])
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run = train.setup(train.parse_args(argv))
    n_layers = run.cfg.n_layers

    def bench_init(key, layers_sharding=None):
        w = wt.draw_all(cfg, n_layers, key, layers_sharding)
        sizes = run.model.stage_sizes
        stages, lo = [], 0
        for n in sizes:
            stages.append({"layers": jax.tree.map(
                lambda a: a[lo:lo + n], w["layers"])})
            lo += n
        return {"outer": w["outer"], "stages": tuple(stages)}

    if not run.rc.execution == "mpmd":
        bench_init = jax.jit(bench_init)
    # the model draws the benchmark's weights: Runtime.init builds the
    # state through its own path (stage-local under mpmd)
    run.model.init = bench_init
    rt = Runtime(run.plan, run.model, run.rc)
    step = rt.train_step
    if step_wrapper is not None:
        step = step_wrapper(step)
    return Program(run, rt, step, out.getvalue())


def init_state(prog: Program, seed: int):
    return prog.runtime.init(wt.weights_key(seed), prog.run.batch_sds)


# ---------------------------------------------------------------- readings
# Every reading is taken on the device that holds the leaf, one leaf at
# a time, so that the readings neither gather a stage's weights onto
# another chip nor raise the peak that the run reports.



def _local(a):
    """``a`` whole as an array on one device (a replicated leaf's first
    copy)."""
    if len(a.devices()) == 1:
        return a
    if not a.sharding.is_fully_replicated:
        raise ValueError(f"leaf of shape {a.shape} is split over devices")
    return a.addressable_shards[0].data


def _stage_rows(stages, sizes) -> Dict[tuple, list]:
    """``{path: [stage k's rows [n_k, ...] on stage k's device]}``: from
    a tuple of per-stage trees, or from the packed mpmd layout
    ``[v, S, Lmax, ...]`` (chunk ``q`` at ``[q // S, q % S]``, dim 1 split
    over the pipe devices), padding dropped."""
    if isinstance(stages, (tuple, list)):
        flat = [wt.flat(t["layers"]) for t in stages]
        return {p: [_local(f[p]) for f in flat] for p in flat[0]}
    out = {}
    for p, a in wt.flat(stages["layers"]).items():
        S = a.shape[1]
        by = {sh.index[1].start or 0: sh.data for sh in a.addressable_shards}
        out[p] = [by[q % S][q // S, 0, :n] for q, n in enumerate(sizes)]
    return out


@jax.jit
def _row_norms(a):
    return jnp.sqrt(jnp.sum(jnp.square(
        a.reshape(a.shape[0], -1).astype(jnp.float32)), 1))


@jax.jit
def _norm(a):
    return jnp.linalg.norm(a.astype(jnp.float32).ravel())


def _norms(params, sizes) -> Dict[str, float]:
    """Per-leaf norms: outer leaves whole, layer leaves per layer, in
    flat layer order."""
    out = {}
    for path, a in wt.flat(params["outer"]).items():
        out["outer/" + "/".join(path)] = float(_norm(_local(a)))
    for path, rows in _stage_rows(params["stages"], sizes).items():
        v = np.concatenate([np.asarray(_row_norms(r)) for r in rows])
        out.update({f"layers/{'/'.join(path)}/{j}": float(x)
                    for j, x in enumerate(v)})
    return out


def check(prog: Program, state, cfg: dict, job: dict, seed: int):
    """Drive ``check_steps(job)`` steps from the seed; returns the state
    the window continues from and the readings: every step's loss
    (``None`` where the schedule logs no valid one yet), the
    momentum's per-leaf norms after the first step in which every leaf
    has a gradient, and the weights' per-leaf change over the steps."""
    n, g_step = check_steps(job), warmup(job) + 1
    sizes = tuple(prog.run.plan.partition.sizes())
    losses, valid, mom = [], [], None
    for t in range(n):
        state, met = prog.step(state, prog.run.data.batch_at(t))
        losses.append(met["loss"])
        valid.append(met["loss_valid"])
        if t + 1 == g_step:
            mom = _norms(state["momentum"], sizes)
    change = _change_norms(state["params"], cfg, seed, sizes)
    loss_h = [float(v) if float(ok) > 0 else None
              for v, ok in zip(jax.device_get(losses),
                               jax.device_get(valid))]
    return state, {"losses": loss_h, "momentum": mom, "change": change}


def _change_norms(params, cfg, seed, sizes) -> Dict[str, float]:
    """Per-leaf ``|W_now - W_0|``, with ``W_0`` drawn again one leaf at
    a time on the device of the leaf it is compared with."""
    key = wt.weights_key(seed)
    outer = wt.flat(params["outer"])
    rows = _stage_rows(params["stages"], sizes)
    out = {}
    for i, (g, path, shape) in enumerate(wt.leaf_table(cfg, sum(sizes))):
        name = f"{g}/{'/'.join(path)}"
        if g == "outer":
            a = _local(outer[path])
            out[name] = float(_diff_norm(a, _on(key, a), i, path))
            continue
        lo, v = 0, []
        for r in rows[path]:
            v.append(np.asarray(_diff_rows(r, _on(key, r), i, path, shape,
                                           lo)))
            lo += r.shape[0]
        out.update({f"{name}/{j}": float(x)
                    for j, x in enumerate(np.concatenate(v))})
    return out


def _on(key, a):
    return jax.device_put(key, next(iter(a.devices())))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _diff_norm(a, key, i, path):
    return _norm(a - wt.draw_leaf(key, i, path, a.shape))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _diff_rows(rows, key, i, path, shape, lo):
    w0 = wt.draw_leaf(key, i, path, shape)[lo:lo + rows.shape[0]]
    return _row_norms(rows - w0)
