"""One run of one cell: set-up, the measured window, the traced
reduction and the comparison with the reference.

Set-up is everything from process start to the first timed step:
imports, plan and schedule verifier, the state's init, compilation or
cache loads, and the steps the reference follows (which warm up every
shape the window uses).  The window then keeps at most one step in
flight, as a loop that logs every loss would: it dispatches step
``t+1`` and then waits for step ``t``'s loss.  ``tokens_per_s`` is
batch x seq of every step completed in the window over the time from
the window's start to the last completion.  The step in flight when
the window closes is waited for and not counted.
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import jax

from bench import cells
from bench import compare
from bench import flops
from bench import program
from bench import trace as tr
from bench.reference.train import Reference, check_steps, warmup

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class _Compiles:
    """Counts programs lowered (a new compile or a cache load) while
    it is on."""
    _one = None

    def __init__(self):
        self.n, self.on = 0, False

    @classmethod
    def get(cls):
        if cls._one is None:
            cls._one = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._one._event)
        cls._one.n = 0
        return cls._one

    def _event(self, event, duration, **kw):
        if self.on and event == LOWERING_EVENT:
            self.n += 1


def window(step: Callable, state, data, first: int, seconds: float):
    """Run the timed loop; returns (state, steps completed, seconds to
    the last completion, their losses)."""
    ann, step_ann = jax.profiler.TraceAnnotation, \
        jax.profiler.StepTraceAnnotation
    done, losses = 0, []
    pending = None
    s = first
    t0 = time.perf_counter()
    t_last = t0
    with ann("bench.window"):
        while True:
            with step_ann("bench.step", step_num=s):
                with ann("bench.data"):
                    batch = data.batch_at(s)
                with ann("bench.dispatch"):
                    state, met = step(state, batch)
                if pending is not None:
                    with ann("bench.wait"):
                        jax.block_until_ready(pending)
                    t_last = time.perf_counter()
                    done += 1
                    losses.append(pending)
                pending = met["loss"]
            s += 1
            if done and t_last - t0 >= seconds:
                break
        jax.block_until_ready(pending)
    return state, done, t_last - t0, losses


def run_cell(spec: dict, seed: int, seconds: float, traced: bool,
             t_start: float, devices, *, smoke: bool = False,
             step_wrapper: Optional[Callable] = None) -> dict:
    """The result line's fields for one run (see ``bench/run.py``)."""
    cfg, job, cell = spec["cfg"], spec["job"], spec["cell"]
    prog = program.build(cfg, job, seed, smoke=smoke,
                         step_wrapper=step_wrapper)
    print(prog.plan_lines, end="")
    plan = prog.run.plan
    predicts = job["mode"] == "spectrain" and any(plan.s_fwd)
    print(f"# prediction lag per stage (s_fwd): {tuple(plan.s_fwd)}; "
          f"weight prediction {'runs' if predicts else 'does not run'}")
    if job["schedule"] != "stream":
        print(f"# plan bubble_frac {plan.bubble_frac}")
    t1 = time.perf_counter()
    state = jax.block_until_ready(program.init_state(prog, seed))
    t2 = time.perf_counter()
    state, prog_read = program.check(prog, state, cfg, job, seed)
    t3 = time.perf_counter()
    print(f"# set-up: to the runtime {t1 - t_start:.1f} s, init "
          f"{t2 - t1:.1f} s, first {check_steps(job)} steps and their "
          f"readings {t3 - t2:.1f} s")
    n_check = check_steps(job)
    tokens_per_step = job["batch"] * job["seq"]

    counter = _Compiles.get()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    setup_s = time.perf_counter() - t_start
    counter.on = True
    if traced:
        jax.profiler.start_trace(tdir)
    try:
        state, done, wall, losses = window(prog.step, state, prog.run.data,
                                           n_check, seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
    counter.on = False
    print(f"# compilations in window: {counter.n}")
    failed = sum(not math.isfinite(float(v)) for v in losses)
    tps = done * tokens_per_step / wall
    used = devices[:cell["chips"]]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used]
    mem = max(peaks)
    print(f"# steps in window {done}, {wall:.3f} s; peak_bytes_in_use "
          f"per device {peaks}")
    fpt = flops.train_flops_per_token(
        cfg, job["stages"] * job["layers_per_stage"], job["seq"])
    print(f"# model FLOPs per token {fpt:.6g}")

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    metrics, breakdown = {}, None
    if traced:
        t = tr.load(next(Path(tdir).rglob("*.xplane.pb")))
        shutil.rmtree(tdir, ignore_errors=True)
        lo, hi = t.window()
        ids = [d.id for d in used]
        devs = [t.devices[i] for i in ids if i in t.devices]
        if not devs:
            raise RuntimeError(f"the trace holds none of devices {ids}")
        ctx = {"window": (lo, hi), "trace_devices": devs,
               "tokens_per_s": tps, "flops_per_token": fpt,
               "chips": cell["chips"],
               "peak_flops": flops.peak(used[0].device_kind)["bf16_flops"]
               if not smoke else 1.0}
        device["busy_s"] = sum(tr.busy(d, lo, hi) for d in devs) \
            / len(devs) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        for m in spec["per_layer"]:
            v = cells.reader(spec["root"], m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        # the idle gaps of the idlest chip, where the pipeline waits
        idlest = min(devs, key=lambda d: tr.busy(d, lo, hi))
        breakdown = {"device_ops": tr.top_ops(dict(enumerate(devs)), lo, hi),
                     "idle_gaps": tr.idle_gaps(idlest, t.spans, lo, hi)}
    else:
        e2e = {"tokens_per_s": tps, "peak_hbm_gib": mem / 2 ** 30,
               "setup_s": setup_s}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # the program's state and compiled steps go before the reference
    # runs, so that the reference neither shares the chip's memory with
    # them nor sets the peak read above
    del state, prog, losses
    gc.collect()
    t_ref = time.perf_counter()
    ref = Reference(cfg, job, seed, used).run(n_check, warmup(job) + 1)
    print(f"# reference: {time.perf_counter() - t_ref:.1f} s")
    nums = compare.numbers(prog_read, ref)
    limits = spec["limits"]
    correct = (compare.verdict(nums, limits) and done > 0 and failed == 0)
    checks = {n: {"value": nums[n] if math.isfinite(nums[n]) else None,
                  "limit": limits.get(n)} for n in compare.NAMES}
    out = {"correct": correct, "attempted": done, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    out["_readings"] = {"program": prog_read, "reference": ref}
    return out


def print_result(out: dict):
    """Stderr's last lines and stdout's last line."""
    import json
    out = {k: v for k, v in out.items() if not k.startswith("_")}
    for n, c in out["checks"].items():
        log(f"check {n} {c['value']!r} limit {c['limit']!r}")
    log(f"correct {out['correct']}")
    sys.stdout.flush()
    print(json.dumps(out), flush=True)


def use_checkout_cache():
    """JAX's persistent compile cache in the checkout's ``.jax_cache``,
    the directory ``repro.launch.compile_cache`` names, even where
    ``JAX_COMPILATION_CACHE_DIR`` points elsewhere: a benchmark run
    shares no cache with another checkout."""
    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
