"""Smoke-width runs of the benchmark's cells, for the CPU tests.

A smoke run is a whole run of a cell (``bench.harness.run_cell``) past
the harness's look for a chip, at the registry's ``smoke_config``
widths in the configuration file's keys, with float32 compute unless
asked otherwise.  ``fault`` breaks the timed path underneath:

``unchanged``    the step returns the state it was given;
``half_batch``   the second half of every batch is replaced by the
                 first, so the mean runs over half of the rows;
``no_exchange``  every ``ppermute`` between chips delivers zeros.

A job that is not a cell yet (its configuration and traffic files are
in ``bench/``, its cell is an open question) runs the same way, held to
the limits of the proven cell ``granite8b-stream-1chip``.

Run as a script for jobs that need several devices (the CPU backend
gets them from ``XLA_FLAGS`` before JAX starts):

    python tests/bench/benchsmoke.py <cell or config:traffic:chips> \
        '<job overrides json>' [fault]

The last line of its output is ``{"correct": ..., "checks": ...}``.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[2]
FAULTS = ("unchanged", "half_batch", "no_exchange")


def smoke_cfg(cfg: dict) -> dict:
    """``cfg`` at the registry's smoke widths: the keys every block
    has, and the block file's own (``smoke``)."""
    from bench import cells
    from repro.configs import get_config, smoke_config
    s = smoke_config(get_config(cfg["repo_arch"]))
    blk = cells.block(cells.ROOT, cfg["block"])
    return dict(cfg, hidden_size=s.d_model, intermediate_size=s.d_ff,
                num_attention_heads=s.n_heads,
                num_key_value_heads=s.n_kv_heads,
                vocab_size=s.vocab_size, vocab_rows=s.vocab_padded,
                **blk.smoke(cfg, s))


PROVEN = "granite8b-stream-1chip"


def spec_of(name: str) -> dict:
    """The cell ``name``, or the job ``config:traffic:chips`` as a cell
    held to the proven cell's limits."""
    from bench import cells
    if ":" not in name:
        return cells.load(ROOT, name)
    config, traffic, chips = name.split(":")
    spec = cells.load(ROOT, PROVEN)
    spec["cfg"] = json.loads(
        (ROOT / f"bench/configs/{config}.json").read_text())
    spec["job"] = json.loads(
        (ROOT / f"bench/traffic/{traffic}.json").read_text())
    spec["cell"] = dict(spec["cell"], name=name, config=config,
                        traffic=traffic, chips=int(chips))
    return spec


def smoke_spec(name: str, compute_dtype: str = "float32", **job) -> dict:
    """``spec_of(name)`` at smoke widths; ``job`` overrides its traffic
    (a smaller batch or sequence)."""
    spec = spec_of(name)
    spec["cfg"] = dict(smoke_cfg(spec["cfg"]), compute_dtype=compute_dtype)
    spec["job"] = dict(spec["job"], **job)
    return spec


def _unchanged(step):
    import jax
    import jax.numpy as jnp

    def f(state, batch):
        _, met = step(jax.tree.map(jnp.copy, state), batch)
        return state, met
    return f


def _half_batch(step):
    import numpy as np

    def f(state, batch):
        h = len(batch["tokens"]) // 2
        return step(state, {k: np.concatenate([v[:h], v[:h]])
                            for k, v in batch.items()})
    return f


@contextlib.contextmanager
def _no_exchange():
    import jax
    import jax.numpy as jnp
    with mock.patch.object(jax.lax, "ppermute",
                           lambda x, *a, **k: jax.tree.map(jnp.zeros_like,
                                                           x)):
        yield


def run(name: str, *, seed: int = 7, fault: str = None,
        seconds: float = 0.2, traced: bool = False, **job) -> dict:
    """One smoke run; the result's fields (``_readings`` included)."""
    import jax
    from bench import harness
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    spec = smoke_spec(name, **job)
    wrapper = {"unchanged": _unchanged,
               "half_batch": _half_batch}.get(fault)
    ctx = _no_exchange() if fault == "no_exchange" \
        else contextlib.nullcontext()
    with ctx, contextlib.redirect_stdout(sys.stderr):
        return harness.run_cell(spec, seed, seconds, traced,
                                time.perf_counter(), jax.devices(),
                                smoke=True, step_wrapper=wrapper)


def control(name: str, *, seed: int = 7, fault: str = None, **job):
    """The control's numbers at smoke width: the reference computed
    from float8 operands (or, with ``fault``, in float32 with that
    fault of ``bench.reference.train.FAULTS`` planted), put in the
    program's place, against the float32 reference; and the cell's
    limits."""
    import jax
    from bench import compare
    from bench.reference.train import Reference, check_steps, warmup
    spec = smoke_spec(name, **job)
    cfg, job = spec["cfg"], spec["job"]
    n, g = check_steps(job), warmup(job) + 1
    devs = jax.devices()
    ref = Reference(cfg, job, seed, devs).run(n, g)
    low = Reference(cfg, job, seed, devs, lowp=fault is None,
                    fault=fault).run(n, g)
    return compare.numbers(low, ref), spec["limits"]


if __name__ == "__main__":
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    fault = sys.argv[3] if len(sys.argv) > 3 else None
    out = run(sys.argv[1], fault=fault, **json.loads(sys.argv[2]))
    print(json.dumps({"correct": out["correct"], "checks": out["checks"]}))
