#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \\
        --control-seeds 1,2,3 --out calib-<cell>.jsonl

In one process, for every seed of ``--seeds``: the program's first
steps exactly as a run takes them (``bench.program.check``), then the
float32 reference, and the three numbers of ``bench.compare`` between
them.  For every seed of ``--control-seeds`` also: the control (the
reference computed from float8 operands, put in the program's place)
and each fault a timed step of the cell can have, planted in the
reference put in the program's place (half of the batch left out; on
more than one chip, the exchange between chips left out; under the
stream schedule, the weight prediction left out).  A state
left unchanged reads 1 on ``update_norm_gap`` and needs no run.  One
JSON line per reading goes to ``--out``; a summary (the largest sound
reading and the smallest control and fault readings per number) is the
last line of standard output.  Runs on the chip at the cell's size; the
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="", dest="control_seeds")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax
    from bench import cells, compare, harness, program
    from bench.reference.train import Reference, check_steps, warmup

    spec = cells.load(ROOT, args.workload)
    harness.use_checkout_cache()
    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        harness.log(f"calibrate: needs {chips} TPU chip(s)")
        return 3
    used = devices[:chips]
    cfg, job = spec["cfg"], spec["job"]
    n, g = check_steps(job), warmup(job) + 1
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = (["half_batch"] + (["no_exchange"] if chips > 1 else [])
              + (["no_prediction"] if job["schedule"] == "stream" else []))
    rows = []
    out = open(args.out, "a")

    def emit(seed, kind, nums, secs):
        row = {"workload": args.workload, "seed": seed, "kind": kind,
               "numbers": nums, "seconds": round(secs, 1)}
        rows.append(row)
        out.write(json.dumps(row) + "\n")
        out.flush()
        print(json.dumps(row), flush=True)

    for seed in seeds:
        t0 = time.perf_counter()
        prog = program.build(cfg, job, seed)
        state = program.init_state(prog, seed)
        state, got = program.check(prog, state, cfg, job, seed)
        del state, prog
        gc.collect()
        t1 = time.perf_counter()
        ref = Reference(cfg, job, seed, used).run(n, g)
        t2 = time.perf_counter()
        emit(seed, "program", compare.numbers(got, ref), t2 - t0)
        print(f"# seed {seed}: program {t1 - t0:.1f} s, reference "
              f"{t2 - t1:.1f} s; losses {got['losses']} vs "
              f"{ref['losses']}", flush=True)
        if seed not in controls:
            continue
        t0 = time.perf_counter()
        low = Reference(cfg, job, seed, used, lowp=True).run(n, g)
        emit(seed, "control", compare.numbers(low, ref),
             time.perf_counter() - t0)
        for f in faults:
            t0 = time.perf_counter()
            bad = Reference(cfg, job, seed, used, fault=f).run(n, g)
            emit(seed, f, compare.numbers(bad, ref), time.perf_counter() - t0)
    out.close()

    summary = {}
    for name in compare.NAMES:
        by = {}
        for r in rows:
            by.setdefault(r["kind"], []).append(r["numbers"][name])
        summary[name] = {"program_max": max(by.get("program", [0])),
                         **{f"{k}_min": min(v) for k, v in by.items()
                            if k != "program"}}
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
