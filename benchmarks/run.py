"""Benchmark orchestrator: one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV; ``--json-out PATH`` also
writes a machine-readable ``{name: us_per_call}`` dump — the format the
CI bench gate (``benchmarks/compare.py``) consumes and the committed
``benchmarks/baseline.json`` was recorded in.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="long training runs for convergence/rmse")
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark names")
    ap.add_argument("--json-out", default="", dest="json_out",
                    help="write {name: us_per_call} JSON to this path")
    args = ap.parse_args()

    from benchmarks import (breakdown, comm_time, comm_volume, convergence,
                            ir_compile, kernel_bench, planner_bench, rmse,
                            roofline, serve_bench, throughput,
                            verifier_bench)
    benches = {
        "comm_volume": comm_volume.main,      # Fig. 3
        "comm_time": comm_time.main,          # Fig. 4
        "throughput": throughput.main,        # Fig. 9
        "breakdown": breakdown.main,          # Fig. 10
        "rmse": rmse.main,                    # Fig. 8
        "convergence": convergence.main,      # Fig. 11 / Table 1
        "kernels": kernel_bench.main,         # Pallas kernels
        "roofline": roofline.main,            # EXPERIMENTS.md §Roofline
        "planner": planner_bench.main,        # EXPERIMENTS.md §Planner
        "ir_compile": ir_compile.main,        # EXPERIMENTS.md §IR backends
        "verifier": verifier_bench.main,      # planner/verify.py gate
        "serve": serve_bench.main,            # docs/SERVING.md
    }
    picked = (args.only.split(",") if args.only else list(benches))
    print("name,us_per_call,derived")
    results = {}
    failures = 0
    for name in picked:
        try:
            for line in benches[name](fast=not args.full):
                print(line)
                parts = line.split(",", 2)
                if len(parts) >= 2:
                    results[parts[0]] = float(parts[1])
        except Exception:
            failures += 1
            print(f"{name}/ERROR,0,exception")
            traceback.print_exc(file=sys.stderr)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
            f.write("\n")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
