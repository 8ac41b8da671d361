"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology that
is described rather than attached; it refuses what the chip would refuse
(block shapes off the (8, 128) tiling, programs that overflow HBM).  So
these tests guard the chip run of ``chip_smoke.py`` at no chip time:

- the one-chip smoke phases' step programs (granite-8b at published
  widths, one layer per stage) on one described chip;
- the four-chip smoke phase's mpmd round on a ``pipe`` mesh over the
  four described chips of a ``v5e:2x2``;
- each Pallas kernel at a model's widths.

Nothing runs, so these say nothing about results or times.  The topology
is described inside a fixture (only one process at a time may load the
TPU library, so nothing may do it while modules are imported), and the
persistent compile cache is off around the compiles: an entry written
for a described chip cannot be read back without one.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

import chip_smoke
from bench import flops
from repro.api import Runtime
from repro.kernels import ops
from repro.launch import train
from repro.runtime.sharding import mpmd_state_shardings

ROOT = Path(__file__).resolve().parents[1]
GiB = 2 ** 30
# HBM a program may use on one v5e chip, as the compiler reports it
# ("Used ... of 15.75G hbm") when it refuses a program
V5E_HBM = 15.75 * GiB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # otherwise the TPU library writes its logs outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compile_step(argv, devices):
    """Compile the train step that ``repro.launch.train`` flags ``argv``
    describe, from shapes alone: on ``devices[0]`` for spmd and stream
    runs, on a ``pipe`` mesh over ``devices`` for mpmd."""
    args = train.parse_args(argv)
    run = train.setup(args)
    mesh = None
    if run.rc.execution == "mpmd":
        mesh = Mesh(np.asarray(devices[:run.plan.n_devices]), ("pipe",))
    rt = Runtime(run.plan, run.model, run.rc, mesh=mesh)
    state = jax.eval_shape(lambda k: rt.init(k, run.batch_sds),
                           jax.random.PRNGKey(args.seed))
    if mesh is not None:
        state_sh = mpmd_state_shardings(mesh, state)
        batch_sh = NamedSharding(mesh, PartitionSpec())
    else:
        batch_sh = SingleDeviceSharding(devices[0])
        state_sh = jax.tree.map(lambda _: batch_sh, state)

    def placed(tree, sh):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sh)

    batch = placed(run.batch_sds,
                   jax.tree.map(lambda _: batch_sh, run.batch_sds))
    compiled = rt.step_fn().lower(placed(state, state_sh), batch).compile()
    return compiled, run


def fits_one_chip(compiled):
    """Arguments plus the temp block the executable allocates fit the
    chip's HBM.  The compiler's own capacity check passed an mpmd round
    whose two came to 17.3 GiB, so the sum is checked here."""
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes <= V5E_HBM


def _param_bytes(model):
    return sum(int(np.prod(s.shape)) * 4
               for s in jax.tree.leaves(jax.eval_shape(
                   model.init, jax.random.PRNGKey(0))))


@pytest.fixture(scope="module")
def one_chip_phase(topo):
    """``phase -> (compiled, run)`` of a one-chip smoke phase, each
    compiled once."""
    done = {}

    def get(phase):
        if phase not in done:
            argv = chip_smoke.phase_argv(phase, batch=chip_smoke.BATCH,
                                         seq=chip_smoke.SEQ, steps=1)
            done[phase] = compile_step(argv, topo.devices[:1])
        return done[phase]
    return get


@pytest.mark.parametrize("phase", ["A", "B"])
def test_one_chip_phase_compiles_at_full_width(one_chip_phase, phase):
    compiled, run = one_chip_phase(phase)
    assert (run.cfg.d_model, run.cfg.d_ff, run.cfg.vocab_size) \
        == (4096, 14336, 49152)
    # float32 params and momentum enter the donated step whole
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * _param_bytes(run.model)
    assert mem.alias_size_in_bytes >= 2 * _param_bytes(run.model)
    assert fits_one_chip(compiled)


def test_stream_tick_computes_little_beyond_the_model(one_chip_phase):
    """Phase A's tick (the stream schedule, two stages of one layer)
    recomputes stage 0's forward in its backward and nothing of the last
    stage's, whose backward applies the vjp of its forward: the compiled
    count stays within 1.12x the model's FLOPs (1.19x when the last
    stage ran its forward twice)."""
    compiled, run = one_chip_phase("A")
    cfg = json.loads((ROOT / "bench" / "configs" / "granite-8b.json")
                     .read_text())
    tokens = chip_smoke.BATCH * chip_smoke.SEQ
    model = flops.train_flops_per_token(cfg, run.cfg.n_layers,
                                        chip_smoke.SEQ) * tokens
    assert compiled.cost_analysis()["flops"] <= 1.12 * model


def test_mpmd_round_compiles_on_four_chips(topo):
    argv = chip_smoke.mpmd_argv(batch=chip_smoke.BATCH, seq=chip_smoke.SEQ)
    compiled, run = compile_step(argv, topo.devices)
    assert run.plan.n_devices == 4
    text = compiled.as_text()
    assert "num_partitions=4" in text
    # the forward and backward rings cross the stage cuts as ppermutes
    assert "collective-permute" in text
    # each device holds its own stage's float32 weights and momentum
    # (plus the replicated embedding and head), not the whole model's
    whole = 2 * _param_bytes(run.model)
    assert compiled.memory_analysis().argument_size_in_bytes < whole / 2
    assert fits_one_chip(compiled)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash(sh):
    # granite-8b: 32 query / 8 KV heads of 128
    q, kv = _sds(sh, (1, 1024, 32, 128)), _sds(sh, (1, 1024, 8, 128))

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v).astype(jnp.float32))

    return jax.jit(jax.grad(loss, (0, 1, 2))), (q, kv, kv)


def _rwkv6(sh):
    # rwkv6-7b: 64 heads of 64
    x = _sds(sh, (1, 1024, 64, 64))
    u = _sds(sh, (64, 64), jnp.float32)
    s0 = _sds(sh, (1, 64, 64, 64), jnp.float32)
    return jax.jit(lambda *a: ops.rwkv6_scan(*a)), (x, x, x, x, u, s0)


def _mamba2(sh):
    # zamba2-1.2b: d_inner 4096 = 64 heads of 64, d_state 64, one group
    x = _sds(sh, (1, 1024, 64, 64))
    dt = _sds(sh, (1, 1024, 64), jnp.float32)
    bc = _sds(sh, (1, 1024, 1, 64))
    s0 = _sds(sh, (1, 64, 64, 64), jnp.float32)
    return jax.jit(lambda *a: ops.mamba2_scan(*a)), (x, dt, dt, bc, bc, s0)


def _fused_update(sh):
    # one granite-8b MLP matrix
    w = _sds(sh, (4096, 14336), jnp.float32)
    return (jax.jit(lambda w, v, g: ops.fused_update(w, v, g, lr=1e-2,
                                                     s=2.0)),
            (w, w, w))


_NO_CUMPROD = pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="Unimplemented primitive in Pallas TPU lowering for "
           "KernelType.TC: cumprod (the chunk's decay products need "
           "another formulation, not a layout change; ROADMAP C4)")


@pytest.mark.parametrize("kernel", [
    "flash_attention",
    pytest.param("rwkv6_scan", marks=_NO_CUMPROD),
    pytest.param("mamba2_scan", marks=_NO_CUMPROD),
    "fused_update",
])
def test_kernel_compiles_at_model_widths(one_chip, kernel):
    fn, args = {"flash_attention": _flash, "rwkv6_scan": _rwkv6,
                "mamba2_scan": _mamba2,
                "fused_update": _fused_update}[kernel](one_chip)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
