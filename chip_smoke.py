#!/usr/bin/env python3
"""Smoke run of the SpecTrain trainer on TPU at granite-8b's published widths.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # four chips: the mpmd phase alone

granite-8b keeps every published width (d_model 4096, 32 query / 8 KV
heads of 128, d_ff 14336, vocab 49152); only the depth is cut.  Weights
are random from ``--seed``.  Tokens come from the i.i.d. ``uniform``
stream: a bigram table at this vocabulary would not fit host memory.

  A           the paper's own path: ``--schedule stream --mode spectrain``,
              2 stages x 1 layer, bf16 compute, through
              ``repro.launch.train.main``
  B           the IR round runtime: ``--schedule 1f1b --execution spmd``,
              same size, same entry point
  four chips  ``--schedule 1f1b --execution mpmd``, 4 stages x 2 layers,
              through ``repro.launch.train.setup`` (``plan()``) and
              ``repro.api.Runtime``; each device creates only its own
              stage's weights, and the first loss is checked against a
              plain float32 ``jax.numpy`` forward that runs each stage on
              the device holding it

Every logged loss must be finite.  At initialisation the logits are
about N(0, 1) per entry (the final rmsnorm gives rows of RMS 1, the
unembedding has std 1/sqrt(d_model)), so the first valid loss must lie
within ``FIRST_LOSS_BAND`` of ln(V) + 1/2, the expected cross-entropy of
such logits against uniform targets.  The streaming schedule's first
``pipe - 1`` ticks read an empty ring slot and are checked for
finiteness only.

Seconds and peak bytes printed on the way are smoke numbers, not a
benchmark.  The script exits non-zero, before any training, when JAX
finds no TPU.  Its last line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

ARCH = "granite-8b"
# batch x seq per step; every phase's compiled step fits one chip's HBM
# at this size (tests/test_tpu_compile.py)
BATCH, SEQ = 8, 1024
STEPS = 4
FIRST_LOSS_BAND = 0.5
# |runtime loss - float32 reference| / reference allowed on the
# four-chip phase: one unit roundoff of bfloat16 (8 significant bits,
# u = 2^-8), the runtime's compute dtype -- about 0.044 at a loss of
# ln(49152) + 1/2.  On CPU at smoke widths the bf16 runtime is 5e-4 from
# the reference and the float32 runtime 3e-7 (tests/test_chip_smoke.py
# holds the latter to 1e-6 relative, which is what shows the reference
# computes the runtime's function).  At random init the loss hardly
# depends on the layers (dropping every layer moves the smoke loss by
# 0.04), so on the chip this check bounds the bf16 error and catches a
# lost or misrouted activation (which gives ln V, 0.5 away), not a
# subtly wrong layer.
REF_LOSS_RTOL = 2.0 ** -8


def _import_repo():
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {SRC}; run "
                         f"this script from a checkout of the repository")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def require_tpu(n_devices: int):
    """The first ``n_devices`` TPU devices, or exit before any training."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < n_devices:
        raise SystemExit(f"chip_smoke: needs {n_devices} TPU devices, JAX "
                         f"found {len(devs)}")
    return devs[:n_devices]


def check_losses(name: str, losses, *, first_valid: int, vocab: int):
    """Every loss finite; ``losses[first_valid]`` near ln(V) + 1/2."""
    bad = [i for i, v in enumerate(losses) if not math.isfinite(v)]
    if bad:
        raise AssertionError(f"{name}: non-finite loss at steps "
                             f"{[i + 1 for i in bad]}: {losses}")
    want = math.log(vocab) + 0.5
    got = losses[first_valid]
    if abs(got - want) > FIRST_LOSS_BAND:
        raise AssertionError(
            f"{name}: first valid loss {got} is not within "
            f"{FIRST_LOSS_BAND} of ln({vocab}) + 1/2 = {want:.4f}")


def hbm_use(devices):
    """``(peak_bytes_in_use, bytes_limit)`` per device id (None where
    not reported)."""
    stats = {d.id: d.memory_stats() or {} for d in devices}
    return {i: (m.get("peak_bytes_in_use"), m.get("bytes_limit"))
            for i, m in stats.items()}


def train_argv(*, layers: int, pipe: int, batch: int, seq: int,
               seed: int = 0, dtype: str = "bfloat16", smoke: bool = False):
    """``repro.launch.train`` flags shared by every phase: granite-8b at
    published widths cut to ``layers``, uniform tokens.  ``smoke``
    shrinks the widths for a CPU run of the same control flow."""
    argv = ["--arch", ARCH, "--layers", str(layers), "--pipe", str(pipe),
            "--dtype", dtype, "--data", "uniform", "--batch", str(batch),
            "--seq", str(seq), "--seed", str(seed)]
    return argv + (["--smoke"] if smoke else [])


# ---------------------------------------------------------------------------
# phases A and B: the training CLI


PHASES = {
    "A": ["--schedule", "stream", "--mode", "spectrain"],
    "B": ["--schedule", "1f1b", "--mode", "spectrain",
          "--execution", "spmd"],
}


def phase_argv(phase: str, *, steps: int, **kw):
    """``repro.launch.train`` flags of phase ``phase`` (A or B): two
    stages of one layer, logging every step as JSON."""
    return (train_argv(layers=2, pipe=2, **kw) + PHASES[phase]
            + ["--steps", str(steps), "--log-every", "1", "--json"])


def run_train_phase(phase: str, argv):
    """Run ``train.main(argv)``; returns the logged losses, one per
    step, after checking them."""
    from repro.launch import train

    args = train.parse_args(argv)
    vocab = train.build(args).vocab_padded
    out = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out):
        rc = train.main(argv)
    wall = time.perf_counter() - t0
    text = out.getvalue()
    print(text, end="")
    if rc != 0:
        raise AssertionError(f"phase {phase}: train.main returned {rc}")
    recs = [json.loads(ln) for ln in text.splitlines() if ln.startswith("{")]
    losses = [r["loss"] for r in recs]
    if len(losses) != args.steps:
        raise AssertionError(f"phase {phase}: {len(losses)} losses logged "
                             f"for {args.steps} steps")
    # the stream schedule's head sees its first microbatch at tick pipe-1
    first_valid = args.pipe - 1 if args.schedule == "stream" else 0
    check_losses(f"phase {phase}", losses, first_valid=first_valid,
                 vocab=vocab)
    # each logged step ends in float(loss), which waits for the device;
    # tok_per_s counts the steps after the first (which compiles) from
    # that step's end, so its inverse gives the time since then
    since = [i * args.batch * args.seq / r["tok_per_s"]
             for i, r in enumerate(recs) if i]
    steps_s = [round(b - a, 3) for a, b in zip([0.0] + since, since)]
    print(f"# smoke phase {phase}: {args.steps} steps in {wall:.1f} s, "
          f"seconds per step after the first {steps_s}, losses {losses}")
    return losses


# ---------------------------------------------------------------------------
# the four-chip phase: plan() -> Runtime under mpmd


def _rmsnorm(x, scale, eps=1e-5):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    import jax.numpy as jnp
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def ref_layer(cfg, p, x):
    """One llama-style block (GQA with RoPE, SwiGLU) in float32."""
    import jax
    import jax.numpy as jnp
    b, s, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    a, m = p["attn"], p["mlp"]
    h = _rmsnorm(x, p["ln1"]["scale"])
    q = _rope((h @ a["wq"]).reshape(b, s, H, hd), cfg.rope_theta)
    k = _rope((h @ a["wk"]).reshape(b, s, KV, hd), cfg.rope_theta)
    v = (h @ a["wv"]).reshape(b, s, KV, hd)
    k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + o.reshape(b, s, H * hd) @ a["wo"]
    h = _rmsnorm(x, p["ln2"]["scale"])
    return x + (jax.nn.silu(h @ m["wg"]) * (h @ m["w1"])) @ m["w2"]


def _on(arr, device):
    """The copy of ``arr`` that ``device`` holds."""
    for s in arr.addressable_shards:
        if s.device == device:
            return s.data
    raise ValueError(f"no shard of {arr.shape} on {device}")


def _stage_on(packed, k, size):
    """Stage ``k``'s layers, read from the packed ``[1, S, Lmax, ...]``
    shard of the device that holds it, and that device."""
    import jax

    def shard(leaf):
        return next(s for s in leaf.addressable_shards
                    if (s.index[1].start or 0) == k)

    tree = jax.tree.map(lambda a: shard(a).data[0, 0, :size], packed)
    return tree, shard(jax.tree.leaves(packed)[0]).device


def reference_loss(cfg, state, batch, n_micro: int):
    """The round's first loss recomputed by :func:`ref_layer` in float32:
    the embedding on the device that holds stage 0, each stage's layers
    on the device that holds them, the activation moved between them by
    ``jax.device_put``, the head on the last stage's device; the mean of
    the ``n_micro`` microbatch losses, as the round computes it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    outer = state["params"]["outer"]
    packed = state["params"]["stages"]["layers"]
    sizes = [int(n) for n in np.asarray(state["chunk_sizes"])]
    stages = [_stage_on(packed, k, n) for k, n in enumerate(sizes)]
    dev0, dev_last = stages[0][1], stages[-1][1]
    tok = _on(outer["embed"]["tok"], dev0)
    unembed = _on(outer["embed"]["unembed"], dev_last)
    ln_f = _on(outer["ln_f"]["scale"], dev_last)

    layer = jax.jit(lambda p, x: ref_layer(cfg, p, x))

    # weights enter as arguments: closed over, they would be baked into
    # the program as constants
    @jax.jit
    def head(x, tgt, ln_f, unembed):
        logits = _rmsnorm(x, ln_f) @ unembed
        gold = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)

    losses = []
    mb = batch["tokens"].shape[0] // n_micro
    with jax.default_matmul_precision("highest"):
        for i in range(n_micro):
            sl = slice(i * mb, (i + 1) * mb)
            x = jnp.take(tok, jax.device_put(batch["tokens"][sl], dev0), 0)
            x = x * math.sqrt(cfg.d_model)
            for tree, dev in stages:
                x = jax.device_put(x, dev)
                for j in range(jax.tree.leaves(tree)[0].shape[0]):
                    x = layer(jax.tree.map(lambda a: a[j], tree), x)
            tgt = jax.device_put(batch["targets"][sl], dev_last)
            losses.append(head(x, tgt, ln_f, unembed))
    return float(np.mean([float(v) for v in losses]))


def mpmd_argv(*, n_stages: int = 4, layers_per_stage: int = 2, **kw):
    """Flags of the four-chip phase: 1F1B SpecTrain under mpmd."""
    return (train_argv(layers=n_stages * layers_per_stage, pipe=n_stages,
                       **kw)
            + ["--schedule", "1f1b", "--mode", "spectrain",
               "--execution", "mpmd"])


def run_mpmd_phase(argv, *, steps: int, rtol: float = REF_LOSS_RTOL):
    """Run ``steps`` mpmd rounds of the run ``argv`` describes; returns
    the losses after checking placement and the first loss."""
    import jax

    from repro.api import Runtime
    from repro.launch import train

    args = train.parse_args(argv)
    run = train.setup(args)
    S, M = run.plan.n_devices, run.plan.round_microbatches
    rt = Runtime(run.plan, run.model, run.rc)

    t0 = time.perf_counter()
    state = jax.block_until_ready(rt.init(jax.random.PRNGKey(args.seed)))
    print(f"# smoke mpmd: init {time.perf_counter() - t0:.1f} s")
    held = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            state["params"]["stages"]):
        devs = {s.device for s in leaf.addressable_shards}
        if len(devs) != S or any(s.data.shape[1] != 1
                                 for s in leaf.addressable_shards):
            raise AssertionError(
                f"stage leaf {jax.tree_util.keystr(path)} is not split one "
                f"stage per device: {sorted(str(d) for d in devs)}")
        held |= devs
    devices = sorted(held, key=lambda d: d.id)
    print(f"# stage weights on {len(devices)} distinct devices: "
          f"{[d.id for d in devices]}")

    ref = reference_loss(run.cfg, state, run.data.batch_at(0), M)
    losses = []
    for s in range(steps):
        t0 = time.perf_counter()
        state, met = rt.train_step(state, run.data.batch_at(s))
        losses.append(float(jax.block_until_ready(met["loss"])))
        print(f"# smoke mpmd step {s + 1}: loss {losses[-1]} "
              f"({time.perf_counter() - t0:.2f} s)")
    tol = rtol * abs(ref)
    print(f"# first loss {losses[0]} vs per-device float32 reference {ref} "
          f"(|diff| {abs(losses[0] - ref):.3g}, tolerance {tol:.3g})")
    print(f"# (peak_bytes_in_use, bytes_limit) per device: "
          f"{hbm_use(devices)}")
    if abs(losses[0] - ref) > tol:
        raise AssertionError(f"first loss {losses[0]} differs from the "
                             f"reference {ref} by more than {tol}")
    check_losses("mpmd", losses, first_valid=0, vocab=run.cfg.vocab_padded)
    return losses


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true", dest="four_chips",
                    help="run only the four-stage mpmd phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _import_repo()
    devices = require_tpu(4 if args.four_chips else 1)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}")
    kw = dict(batch=BATCH, seq=SEQ, seed=args.seed)
    if args.four_chips:
        run_mpmd_phase(mpmd_argv(**kw), steps=STEPS)
    else:
        for phase in ("A", "B"):
            run_train_phase(phase, phase_argv(phase, steps=STEPS, **kw))
            print(f"# (peak_bytes_in_use, bytes_limit) after phase "
                  f"{phase}: {hbm_use(devices)}")
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
