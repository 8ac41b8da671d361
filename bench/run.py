#!/usr/bin/env python3
"""The chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout; the cells are listed in
``BENCHMARK.json``.  The run builds the cell's training job through the
repo's own launcher (``repro.launch.train.setup`` and
``repro.api.Runtime``), trains for ``--seconds`` on weights and tokens
drawn from ``--seed``, compares the steps it took first with a plain
float32 reference, and prints as its last line one JSON object:
``correct``, ``attempted`` and ``failed`` (steps in the window, and
those whose loss was not finite), ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics from a profiler
trace of the window), ``device`` and, last, ``checks``: each number
compared with the reference beside its limit.  Those numbers are also
the last lines on standard error.

It exits non-zero without a result where JAX finds no TPU or fewer
chips than the cell asks for.  JAX's compile cache is kept in
``.jax_cache`` inside the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import cells, harness
    spec = cells.load(ROOT, args.workload)
    harness.use_checkout_cache()
    import jax
    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        harness.log(f"bench: {args.workload} needs {chips} TPU chip(s); "
                    f"JAX found {len(devices)} {devices[0].platform} "
                    f"device(s) ({devices[0].device_kind})")
        return 3
    out = harness.run_cell(spec, args.seed, args.seconds,
                           bool(args.trace), T_START, devices)
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
