#!/usr/bin/env python3
"""Where a cell's step spends its device time, by phase and stage.

    python3 bench/phase_breakdown.py --workload <cell> --seed <n> \\
        --seconds <s> [--out DIR]

Run from the root of a checkout, on the chip.  It builds and warms the
cell as ``bench/run.py`` does (set-up and the steps the reference
follows), profiles one window of ``--seconds`` with the harness's own
loop, and prints, from that window's trace and the step program's
phase scopes (``repro.obs.phases``):

* the shares ``bench/phases.py`` reports (% of the window);
* milliseconds per step of each phase x stage, an operation counting
  under one where all its instructions carry it;
* the five operations with the most time whose instructions carry two
  or more phases, with their phase sets;
* the operations that compute the weight prediction, by stage.

``--out DIR`` writes the trace (``<cell>-scoped.xplane.pb``) and beside
it the JSON that ``tests/bench`` pins a recorded run with: its context
and phase tables, and what every per-layer reader read.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import cells, flops, harness, phases, program
    from bench import trace as tr
    from bench.reference.train import check_steps
    spec = cells.load(ROOT, args.workload)
    harness.use_checkout_cache()
    import jax
    from repro.obs import phases as scopes
    used = jax.devices()[:spec["cell"]["chips"]]
    if used[0].platform != "tpu" or len(used) < spec["cell"]["chips"]:
        harness.log(f"{args.workload} needs {spec['cell']['chips']} TPU "
                    f"chip(s)")
        return 3
    cfg, job = spec["cfg"], spec["job"]
    prog = program.build(cfg, job, args.seed)
    state = program.init_state(prog, args.seed)
    state, _ = program.check(prog, state, cfg, job, args.seed)
    n = check_steps(job)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            state, done, wall, _ = harness.window(
                prog.step, state, prog.run.data, n, args.seconds)
        finally:
            jax.profiler.stop_trace()
        xplane = next(Path(d).rglob("*.xplane.pb"))
        t = tr.load(xplane)
        if args.out:
            Path(args.out).mkdir(parents=True, exist_ok=True)
            shutil.copy(xplane, Path(args.out)
                        / f"{args.workload}-scoped.xplane.pb")
    lo, hi = t.window()
    ids = [dev.id for dev in used]
    devs = [t.devices[i] for i in ids]
    t0 = time.perf_counter()
    tables = scopes.step_programs()
    t_tables = time.perf_counter() - t0
    text = prog.runtime.compiled_step().as_text()
    tps = done * job["batch"] * job["seq"] / wall
    print(f"# {args.workload} seed {args.seed}: {done} steps in "
          f"{wall:.3f} s ({tps:.1f} tokens/s); step_programs() "
          f"{t_tables:.3f} s")

    ctx = {"window": (lo, hi), "trace_devices": devs,
           "tokens_per_s": tps,
           "flops_per_token": flops.train_flops_per_token(
               cfg, job["stages"] * job["layers_per_stage"], job["seq"]),
           "chips": len(used),
           "peak_flops": flops.peak(used[0].device_kind)["bf16_flops"],
           "phase_tables": phases.to_json(tables)}
    shares = phases.shares(dict(ctx))
    busy = sum(tr.busy(dev, lo, hi) for dev in devs) / len(devs)
    print("# shares of the window (%): " + ", ".join(
        f"{c} {v:.3f}" for c, v in shares.items())
        + f"; busy {100 * busy / (hi - lo):.3f}")

    for line in breakdown(devs, lo, hi, text, tables):
        print(line)

    if args.out:
        read = {m["name"]: cells.reader(ROOT, m["name"])(dict(ctx))
                for m in spec["per_layer"]}
        rec = {"workload": args.workload, "seed": args.seed,
               "device_ids": ids,
               "context": {k: v for k, v in ctx.items()
                           if k not in ("window", "trace_devices")},
               "metrics": {k: v for k, v in read.items() if v is not None},
               "shares": shares,
               "top_ops": [op for op, _ in tr.top_ops(
                   dict(enumerate(devs)), lo, hi)]}
        (Path(args.out) / f"{args.workload}-scoped.json").write_text(
            json.dumps(rec, indent=1))
    return 0


def breakdown(devs, lo, hi, text, tables):
    """The report's lines after the shares, from the window ``lo, hi``
    of ``devs``, the step program's HLO ``text`` and the phase tables."""
    from bench import phases
    from bench import trace as tr
    from repro.obs import phases as scopes
    module = scopes.module_name(text)
    table = tables[module]
    by_scope = scopes.op_phases(text, scopes.scope_of)
    # an operation of one phase splits further by stage where all its
    # instructions carry one stage scope of that phase
    split = {}
    for k, ps in table.items():
        if phases.classify(ps) in phases.PHASES:
            ps = by_scope[k] if len(by_scope[k]) == 1 \
                else {f"{next(iter(ps))}/stages"}
        split[k] = ps
    ns, op_ns = defaultdict(float), defaultdict(float)
    for dev in devs:
        ops = phases.classes(dev, lo, hi, {module: split})
        for c, v in phases.innermost(ops).items():
            ns[c] += v / len(devs)
        for name, s, e in dev.ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                op_ns[name] += (e - s) / len(devs)
    # the window in steps: over the mean device time of one execution
    runs = [e - s for name, s, e in devs[0].modules
            if name == tr.step_module(devs[0])]
    steps = (hi - lo) / (sum(runs) / len(runs))

    def ms(v):
        return f"{v * 1e-6 / steps:9.3f} ms"

    label = devs[0].labels
    out = [f"# ms per step by phase x stage ({steps:.2f} steps)"]
    out += [f"{c:18s} {ms(v)} {100 * v / (hi - lo):7.3f} %"
            for c, v in sorted(ns.items(), key=lambda kv: -kv[1])]
    out.append("# the five mixed operations with the most time")
    mixed = sorted(((v, k) for k, v in op_ns.items()
                    if len(table[k]) > 1), reverse=True)[:5]
    out += [f"{ms(v)}  {'+'.join(sorted(table[k]))}  {label[k][:120]}"
            for v, k in mixed]
    out.append("# operations that compute the prediction, by stage")
    pred = sorted((sorted(p for p in by_scope[k] if p.startswith("predict")),
                   -v, k) for k, v in op_ns.items()
                  if any(p.startswith("predict") for p in by_scope[k]))
    out += [f"{'+'.join(ps):18s} {ms(-v)}  {'+'.join(sorted(table[k]))}  "
            f"{label[k][:100]}" for ps, v, k in pred]
    return out

if __name__ == "__main__":
    raise SystemExit(main())
