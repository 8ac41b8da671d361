"""True MPMD execution: stage-local weights, bitwise-identical training.

Three layers of evidence for ``execution="mpmd"`` in
``core/pipeline_stream.make_ir_train_step``:

  * **Device streams** — lowering the round event table to per-device
    int32 streams is structurally sound: every device runs T ticks,
    branch ids index the stream's branch set (or the NOP), receive
    slots index the pools, and the ticks run every compute event
    exactly once.
  * **Bit identity** — the shard_map round (stage weights resident
    only on their pipe device, activations/cotangents crossing stage
    cuts via ppermute) is bitwise identical to the SPMD scan backend
    (losses and every state leaf) over {1f1b, 2bw, interleaved,
    gpipe} × ragged DP partitions in spectrain and pipedream modes.
    S = 1 cases run the same ring machinery on a single device, so the
    identity holds in plain single-device CI too.
  * **Gates** — unsupported combinations (clip, hybrid stage trees,
    meshes that do not match the plan) fail loudly, not wrongly.
"""
import numpy as np
import pytest

import jax

from conftest import lm_batch, tiny_cfg
from repro.core import pipeline_stream
from repro.models import Model
from repro.models.model import unpack_chunk_params
from repro.planner import plan, synthetic_profile
from repro.planner import schedule_ir as sir


def _skew(L):
    return [9.0] + [1.0] * (L - 1)


def _mk_plan(schedule, S, v=1, M=4, L=4, partitioner="dp"):
    return plan(profile=synthetic_profile(_skew(L)), n_stages=S,
                schedule=schedule, virtual_stages=v, n_microbatches=M,
                partitioner=partitioner)


def _run(exec_, p, mode, steps=2, lr=0.05):
    cfg = tiny_cfg("granite-8b", n_layers=p.partition.n_layers,
                   pipe=p.n_stages)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = lm_batch(jax.random.PRNGKey(1), cfg,
                     batch=2 * p.round_microbatches, seq=8)
    state = pipeline_stream.make_ir_state(m, params, None, plan=p,
                                          mode=mode, execution=exec_)
    step = jax.jit(pipeline_stream.make_ir_train_step(
        m, plan=p, mode=mode, lr=lr, execution=exec_))
    losses = []
    for _ in range(steps):
        state, met = step(state, batch)
        losses.append(np.asarray(met["loss"]))
    return losses, state


def _assert_states_match(mpmd_state, spmd_state):
    """Unpack the packed stage leaves and require every corresponding
    leaf bit-equal to the SPMD state's ragged chunk trees."""
    sizes = np.asarray(mpmd_state["chunk_sizes"])

    def cmp_tree(pm, ps):
        chunks = unpack_chunk_params(pm["stages"], sizes)
        for q in range(len(sizes)):
            for a, b in zip(jax.tree.leaves(chunks[q]),
                            jax.tree.leaves(ps["stages"][q])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(pm["outer"]),
                        jax.tree.leaves(ps["outer"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    cmp_tree(mpmd_state["params"], spmd_state["params"])
    cmp_tree(mpmd_state["momentum"], spmd_state["momentum"])
    assert ("stash" in mpmd_state) == ("stash" in spmd_state)
    if "stash" in spmd_state:
        cmp_tree(mpmd_state["stash"]["params"],
                 spmd_state["stash"]["params"])
        cmp_tree(mpmd_state["stash"]["momentum"],
                 spmd_state["stash"]["momentum"])
    assert int(mpmd_state["step"]) == int(spmd_state["step"])


# ===========================================================================
# device-stream lowering
# ===========================================================================


class TestDeviceStreams:
    @pytest.mark.parametrize("schedule,S,v,M", [
        ("1f1b", 2, 1, 4), ("1f1b", 4, 1, 8), ("gpipe", 3, 1, 6),
        ("2bw", 3, 1, 6), ("interleaved", 2, 2, 4),
    ])
    def test_structure(self, schedule, S, v, M):
        p = _mk_plan(schedule, S, v=v, M=M, L=S * v)
        ds = p.device_streams()
        T = ds.rows.shape[0]
        assert ds.rows.shape == (T, S, sir.DN_COLS)
        assert ds.rows.dtype == np.int32
        nop = len(ds.branches)
        assert (ds.rows[:, :, sir.DCOL_BRANCH] <= nop).all()
        # every compute event of the round appears exactly once
        C = p.n_chunks
        assert (ds.rows[:, :, sir.DCOL_BRANCH] < nop).sum() == 2 * M * C
        # receive slots index the pools (or -1 = discard)
        assert (ds.rows[:, :, sir.DCOL_RECV_F] < ds.n_val_slots).all()
        assert (ds.rows[:, :, sir.DCOL_RECV_B] < ds.n_cot_slots).all()
        assert (ds.rows[:, :, sir.DCOL_RECV_F] >= -1).all()
        assert (ds.rows[:, :, sir.DCOL_RECV_B] >= -1).all()
        # the head/embed first-contribution markers appear exactly once
        assert (ds.rows[:, :, sir.DCOL_FIRST_O] > 0).sum() == 1
        assert (ds.rows[:, :, sir.DCOL_FIRST_E] > 0).sum() == 1

    def test_deterministic(self):
        a = _mk_plan("1f1b", 3, M=6, L=6).device_streams()
        b = _mk_plan("1f1b", 3, M=6, L=6).device_streams()
        assert a.branches == b.branches
        np.testing.assert_array_equal(a.rows, b.rows)

    def test_tick_groups_cover_events(self):
        """The ticks of the device streams run every compute event of
        the round program exactly once, on the device of its chunk."""
        for schedule, S, v in (("1f1b", 2, 1), ("2bw", 3, 1),
                               ("interleaved", 2, 2)):
            p = _mk_plan(schedule, S, v=v, M=2 * S, L=2 * S * v)
            ds = p.device_streams()
            nop = len(ds.branches)
            ran = []
            for t in range(ds.rows.shape[0]):
                for d in range(S):
                    b = int(ds.rows[t, d, sir.DCOL_BRANCH])
                    if b == nop:
                        continue
                    kind, q, s = ds.branches[b]
                    assert q % S == d
                    ran.append((kind, int(ds.rows[t, d, sir.DCOL_MB]),
                                q, s))
            assert sorted(ran) == sorted(p.round_program())


# ===========================================================================
# bit identity vs the SPMD scan backend
# ===========================================================================


class TestMpmdBitIdentity:
    @pytest.mark.parametrize("schedule,S,v,M,L", [
        ("1f1b", 2, 1, 4, 4),
        ("1f1b", 3, 1, 3, 5),
        ("2bw", 2, 1, 4, 4),
        ("2bw", 3, 1, 3, 5),
        ("interleaved", 2, 2, 4, 4),
        ("interleaved", 3, 2, 3, 6),
        ("gpipe", 2, 1, 4, 4),
    ])
    @pytest.mark.parametrize("mode", ["spectrain", "pipedream"])
    def test_mpmd_matches_scan_bitwise(self, schedule, S, v, M, L, mode):
        """The acceptance criterion: stage-local MPMD execution is
        bit-for-bit the same training as the replicated SPMD scan on
        ragged DP-partitioned plans."""
        if jax.device_count() < S:
            pytest.skip(f"needs >= {S} devices "
                        f"(XLA_FLAGS=--xla_force_host_platform_"
                        f"device_count={S})")
        p = _mk_plan(schedule, S, v=v, M=M, L=L)
        if v == 1 and schedule != "gpipe":
            assert len(set(p.partition.sizes())) > 1, \
                "sweep must exercise a ragged partition"
        ls, ss = _run("spmd", p, mode)
        lm, sm = _run("mpmd", p, mode)
        for a, b in zip(ls, lm):
            assert a.tobytes() == b.tobytes(), (a, b)
        _assert_states_match(sm, ss)

    @pytest.mark.parametrize("schedule,v", [
        ("1f1b", 1), ("2bw", 1), ("interleaved", 2), ("gpipe", 1),
    ])
    def test_single_device_ring_bitwise(self, schedule, v):
        """S = 1 folds every chunk onto one device: the ppermute rings
        degenerate to same-tick self-receives, and the identity must
        still hold — this is the tier-1 (single-device CI) coverage."""
        p = _mk_plan(schedule, 1, v=v, M=4, L=4, partitioner="uniform")
        ls, ss = _run("spmd", p, "spectrain")
        lm, sm = _run("mpmd", p, "spectrain")
        for a, b in zip(ls, lm):
            assert a.tobytes() == b.tobytes(), (a, b)
        _assert_states_match(sm, ss)


# ===========================================================================
# gates
# ===========================================================================


class TestMpmdGates:
    def _model(self, L=4, pipe=1):
        cfg = tiny_cfg("granite-8b", n_layers=L, pipe=pipe)
        return Model(cfg)

    def test_unknown_exec_rejected(self):
        p = _mk_plan("1f1b", 1, partitioner="uniform")
        with pytest.raises(ValueError, match="execution"):
            pipeline_stream.make_ir_train_step(
                self._model(), plan=p, mode="spectrain", lr=0.05,
                execution="simd")

    def test_clip_not_supported(self):
        p = _mk_plan("1f1b", 1, partitioner="uniform")
        with pytest.raises(NotImplementedError, match="clip"):
            pipeline_stream.make_ir_train_step(
                self._model(), plan=p, mode="spectrain", lr=0.05,
                execution="mpmd", clip=1.0)

    def test_mesh_must_match_plan(self):
        from jax.sharding import Mesh
        p = _mk_plan("1f1b", 1, partitioner="uniform")
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
        with pytest.raises(ValueError, match="pipe"):
            pipeline_stream.make_ir_train_step(
                self._model(), plan=p, mode="spectrain", lr=0.05,
                execution="mpmd", mesh=mesh)

    def test_stage_submeshes_raises_without_pipe(self):
        from jax.sharding import Mesh
        from repro.runtime import sharding as sh
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
        with pytest.raises(ValueError, match="pipe"):
            sh.stage_submeshes(mesh, 2)


# ===========================================================================
# CLI
# ===========================================================================


class TestCLIExecFlag:
    def test_mpmd_backend_trains(self):
        from repro.launch import train
        rc = train.main([
            "--arch", "granite-8b", "--smoke", "--pipe", "1",
            "--layers", "4", "--steps", "2", "--batch", "8",
            "--seq", "16", "--log-every", "1",
            "--schedule", "1f1b", "--execution", "mpmd"])
        assert rc == 0

    def test_mpmd_rejects_stream_and_clip(self):
        from repro.launch import train
        with pytest.raises(SystemExit):
            train.main(["--smoke", "--schedule", "stream",
                        "--execution", "mpmd"])
        with pytest.raises(SystemExit):
            train.main(["--smoke", "--schedule", "1f1b", "--pipe", "1",
                        "--execution", "mpmd", "--clip", "1.0"])
