"""Continuous-batching serving driver on the pipelined runtime.

A seeded Poisson arrival trace (``serve/trace.py``) is driven through
the ``repro.api.Runtime`` facade: the default pipelined engine
compiles each serving round to the planner's schedule IR and executes
it under ``--execution spmd`` (the ``lax.scan`` interpreter) or
``--execution mpmd`` (stage-local shard_map over the pipe mesh axis) —
the emitted tokens are bitwise-identical across the two.  ``--engine
simple`` (auto-selected for hybrid / enc-dec archs, whose decode state
the stage split cannot page) serves each request independently through
the whole-model ``decode_step``; its prefill consumes the whole prompt
in one jitted call, not one dispatch per token.

Reported rates exclude XLA compilation (both engines warm up on
throwaway caches first); ``--metrics-out`` appends the scheduler's
admit/decode/evict event log, per-token latency histograms and the
summary record as JSONL.

Example (two stages, 32 requests):
    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b \
        --pipe 2 --layers 4 --requests 32 --rate 1.5
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.api import add_runtime_args, runtime_config_from_args, Runtime
from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.obs import MetricsRegistry
from repro.planner import serve_plan
from repro.serve import SimpleEngine, poisson_trace


def _pair(s: str):
    lo, hi = (int(x) for x in s.split(","))
    return lo, hi


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--pipe", type=int, default=2,
                    help="pipeline stages the serving rounds fold over")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--engine", default="auto",
                    choices=("auto", "pipelined", "simple"),
                    help="'pipelined' runs rounds through the schedule "
                         "IR; 'simple' serves each request through the "
                         "whole-model decode_step; 'auto' picks "
                         "pipelined except for hybrid/enc-dec archs")
    ap.add_argument("--requests", type=int, default=8,
                    help="trace length (seeded Poisson arrivals)")
    ap.add_argument("--rate", type=float, default=1.0,
                    help="mean arrivals per round")
    ap.add_argument("--prompt-lens", type=_pair, default=(2, 12),
                    dest="prompt_lens", metavar="LO,HI",
                    help="inclusive prompt-length range")
    ap.add_argument("--gen-lens", type=_pair, default=(1, 8),
                    dest="gen_lens", metavar="LO,HI",
                    help="inclusive generation-length range")
    ap.add_argument("--slots", type=int, default=4,
                    help="live-request slots (decode wave width)")
    ap.add_argument("--max-prefill", type=int, default=2,
                    dest="max_prefill",
                    help="prompts admitted per round (prefill lanes)")
    ap.add_argument("--prompt-budget", type=int, default=16,
                    dest="prompt_budget",
                    help="padded per-lane prompt buffer")
    ap.add_argument("--page-seq", type=int, default=64, dest="page_seq",
                    help="KV positions per page (caps prompt + gen)")
    ap.add_argument("--pages", type=int, default=0,
                    help="KV pages per stage (default: --slots)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-rounds", type=int, default=0,
                    dest="max_rounds",
                    help="abort if the trace does not drain in this "
                         "many rounds (0: auto bound)")
    ap.add_argument("--metrics-out", default="", dest="metrics_out",
                    help="append scheduler events, latency histograms "
                         "and the summary record as JSONL to this path")
    add_runtime_args(ap, serving=True)
    args = ap.parse_args(argv)
    try:
        rc = runtime_config_from_args(args)
    except ValueError as e:
        raise SystemExit(str(e))

    registry = MetricsRegistry(jsonl_path=args.metrics_out or None)
    try:
        cfg = smoke_config(get_config(args.arch))
        kw = {}
        if args.layers:
            kw["n_layers"] = args.layers
        import dataclasses
        kw["mesh_plan"] = dataclasses.replace(cfg.mesh_plan,
                                              pipe=args.pipe, tensor=1)
        cfg = cfg.replace(**kw)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(args.seed))

        engine_kind = args.engine
        if engine_kind == "auto":
            engine_kind = ("simple" if cfg.is_encdec or model.hybrid
                           else "pipelined")
        if engine_kind == "pipelined" and (cfg.is_encdec or model.hybrid):
            raise SystemExit(
                f"--engine pipelined cannot serve {cfg.name}: hybrid/"
                f"enc-dec decode state is not per-layer pageable; use "
                f"--engine simple (or auto)")

        splan = serve_plan(
            cfg, n_stages=args.pipe, n_slots=args.slots,
            max_prefill=args.max_prefill,
            prompt_budget=args.prompt_budget,
            n_pages=args.pages or None, page_seq=args.page_seq,
            n_layers=cfg.n_layers, validate=engine_kind == "pipelined")
        trace = poisson_trace(
            args.requests, rate=args.rate, seed=args.seed,
            prompt_lens=args.prompt_lens, gen_lens=args.gen_lens,
            vocab=cfg.vocab_size)
        print(f"# {splan.summary()}")
        print(f"# arch={cfg.name} engine={engine_kind} "
              f"execution={rc.execution} requests={len(trace)} "
              f"rate={args.rate} seed={args.seed}")

        if engine_kind == "pipelined":
            rt = Runtime(splan, model, rc, registry=registry)
            engine = rt.serve_engine(params)
        else:
            engine = SimpleEngine(model, params, splan,
                                  registry=registry)
        t0 = time.time()
        results = engine.run(trace,
                             max_rounds=args.max_rounds or None)
        wall_s = time.time() - t0

        served = {r: t for r, t in results.items() if t}
        rejected = sorted(r for r, t in results.items() if not t)
        n_tokens = sum(len(t) for t in served.values())
        hist = registry.histogram("serve/token_ms")
        p50 = hist.percentile(50.0)
        p99 = hist.percentile(99.0)
        compile_s = registry.gauge("serve/compile_s").value or 0.0
        tok_per_s = n_tokens / max(wall_s, 1e-9)
        registry.gauge("serve/wall_s").set(wall_s)
        registry.gauge("serve/tok_per_s").set(tok_per_s)
        registry.emit(
            "serve_run", arch=cfg.name, engine=engine_kind,
            execution=rc.execution, n_requests=len(trace),
            n_served=len(served), n_rejected=len(rejected),
            n_tokens=n_tokens, rate=args.rate, seed=args.seed,
            wall_s=wall_s, compile_s=compile_s,
            tok_per_s=tok_per_s, token_ms_p50=p50, token_ms_p99=p99)
        print(f"compile: {compile_s:.2f}s   "
              f"decode: {tok_per_s:.1f} tok/s   "
              f"p50: {p50:.2f} ms/tok   p99: {p99:.2f} ms/tok")
        print(f"served {len(served)}/{len(trace)} requests "
              f"({len(rejected)} rejected), {n_tokens} tokens "
              f"in {wall_s:.2f}s")
        first = min(served) if served else None
        if first is not None:
            print(f"sample (rid {first}):",
                  list(served[first])[:16])
        assert all(np.isfinite(v) for v in (tok_per_s, p50, p99))
        return 0
    finally:
        registry.close()


if __name__ == "__main__":
    raise SystemExit(main())
