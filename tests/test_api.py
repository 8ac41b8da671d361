"""The unified Runtime facade: RuntimeConfig validation, the shared
argparse wiring, the exec= -> execution= deprecation shims, and
bit-identity of the facade against the legacy constructors."""
import argparse
import dataclasses

import jax
import numpy as np
import pytest

from conftest import lm_batch, tiny_cfg
from repro.api import (Runtime, RuntimeConfig, add_runtime_args,
                       runtime_config_from_args)
from repro.core import pipeline_stream as ps
from repro.models import Model
from repro.planner import plan, serve_plan
from repro.runtime import elastic


def _parser(serving=False):
    ap = argparse.ArgumentParser()
    add_runtime_args(ap, serving=serving)
    return ap


@pytest.fixture(scope="module")
def ir_setup():
    cfg = tiny_cfg("granite-8b", n_layers=4, pipe=2)
    m = Model(cfg)
    p = plan(None, n_stages=2, n_microbatches=4, n_layers=4,
             schedule="1f1b")
    batch = lm_batch(jax.random.PRNGKey(1), cfg, batch=4, seq=8)
    return m, p, batch


class TestRuntimeConfig:
    def test_defaults_valid(self):
        c = RuntimeConfig()
        assert (c.mode, c.execution, c.backend) == \
            ("spectrain", "spmd", "scan")
        assert c.schedule is None

    @pytest.mark.parametrize("kw,msg", [
        (dict(mode="nope"), "unknown mode"),
        (dict(schedule="nope"), "unknown schedule"),
        (dict(backend="nope"), "unknown backend"),
        (dict(execution="simd"), "unknown execution"),
        (dict(execution="mpmd", schedule="stream"), "SPMD-only"),
        (dict(execution="mpmd", clip=1.0), "clip"),
        (dict(ticks_per_step=0), "ticks_per_step"),
    ])
    def test_post_init_rejects(self, kw, msg):
        with pytest.raises(ValueError, match=msg):
            RuntimeConfig(**kw)

    def test_replace_revalidates(self):
        c = RuntimeConfig(schedule="1f1b")
        assert c.replace(lr=0.5).lr == 0.5
        with pytest.raises(ValueError):
            c.replace(mode="nope")

    def test_frozen(self):
        with pytest.raises(dataclasses.FrozenInstanceError):
            RuntimeConfig().lr = 2.0


class TestArgparseWiring:
    def test_training_flags_roundtrip(self):
        args = _parser().parse_args(
            ["--mode", "pipedream", "--schedule", "1f1b",
             "--ir-backend", "unrolled", "--execution", "mpmd",
             "--lr", "0.05", "--gamma", "0.8", "--no-verify"])
        c = runtime_config_from_args(args)
        assert c.mode == "pipedream" and c.schedule == "1f1b"
        assert c.backend == "unrolled" and c.execution == "mpmd"
        assert c.lr == 0.05 and c.gamma == 0.8 and not c.verify

    def test_serving_parser_has_no_training_flags(self):
        ap = _parser(serving=True)
        with pytest.raises(SystemExit):
            ap.parse_args(["--mode", "spectrain"])
        c = runtime_config_from_args(ap.parse_args([]))
        assert c.execution == "spmd" and c.schedule is None

    def test_clip_zero_means_none(self):
        args = _parser().parse_args(["--schedule", "1f1b"])
        assert runtime_config_from_args(args).clip is None

    def test_legacy_exec_flag_warns(self):
        args = _parser().parse_args(
            ["--schedule", "1f1b", "--exec", "mpmd"])
        with pytest.warns(DeprecationWarning, match="--exec"):
            c = runtime_config_from_args(args)
        assert c.execution == "mpmd"

    def test_conflicting_exec_spellings_exit(self):
        args = _parser().parse_args(
            ["--schedule", "1f1b", "--execution", "spmd",
             "--exec", "mpmd"])
        with pytest.warns(DeprecationWarning):
            with pytest.raises(SystemExit, match="conflicts"):
                runtime_config_from_args(args)

    def test_overrides_win(self):
        args = _parser().parse_args(["--schedule", "1f1b"])
        c = runtime_config_from_args(args, ticks_per_step=3)
        assert c.ticks_per_step == 3


class TestKwargShims:
    """exec= stays a one-release DeprecationWarning alias for
    execution= on the legacy constructors, bit-identical."""

    def test_make_ir_state_exec_warns(self, ir_setup):
        m, p, _ = ir_setup
        params = m.init(jax.random.PRNGKey(0))
        with pytest.warns(DeprecationWarning, match="execution"):
            legacy = ps.make_ir_state(m, params, None, plan=p,
                                      exec="spmd")
        new = ps.make_ir_state(m, params, None, plan=p,
                               execution="spmd")
        jax.tree.map(np.testing.assert_array_equal,
                     legacy["params"], new["params"])

    def test_make_ir_train_step_exec_warns(self, ir_setup):
        m, p, _ = ir_setup
        with pytest.warns(DeprecationWarning, match="execution"):
            ps.make_ir_train_step(m, plan=p, mode="spectrain",
                                  lr=0.05, exec="spmd")

    def test_elastic_restate_exec_warns(self, ir_setup):
        m, _, batch = ir_setup
        sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
        state = ps.init_state(m, jax.random.PRNGKey(0), sds)
        with pytest.warns(DeprecationWarning, match="execution"):
            elastic.elastic_restate(m, m, state, sds, exec="spmd")

    def test_conflicting_kwargs_raise(self, ir_setup):
        m, p, _ = ir_setup
        with pytest.warns(DeprecationWarning):
            with pytest.raises(TypeError, match="both"):
                ps.make_ir_train_step(m, plan=p, mode="spectrain",
                                      lr=0.05, exec="mpmd",
                                      execution="spmd")

    def test_unknown_legacy_kwarg_raises(self, ir_setup):
        m, p, _ = ir_setup
        with pytest.raises(TypeError, match="unexpected"):
            ps.make_ir_train_step(m, plan=p, mode="spectrain",
                                  lr=0.05, excc="spmd")


class TestRuntimeFacade:
    def test_needs_a_plan(self):
        m = Model(tiny_cfg("granite-8b", n_layers=2, pipe=2))
        with pytest.raises(TypeError, match="PipelinePlan or ServePlan"):
            Runtime("1f1b", m)

    def test_schedule_cross_check(self, ir_setup):
        m, p, _ = ir_setup
        with pytest.raises(ValueError, match="does not match"):
            Runtime(p, m, RuntimeConfig(schedule="gpipe"))
        Runtime(p, m, RuntimeConfig(schedule="1f1b"))   # matching: fine
        Runtime(p, m)                                   # None adopts

    def test_workload_dispatch_is_typed(self, ir_setup):
        m, p, _ = ir_setup
        splan = serve_plan(None, n_slots=2, max_prefill=1,
                           prompt_budget=8, page_seq=32, n_layers=4)
        rt_t = Runtime(p, m)
        rt_s = Runtime(splan, m)
        with pytest.raises(TypeError, match="ServePlan"):
            rt_t.serve_engine(None)
        with pytest.raises(TypeError, match="serve_step"):
            rt_s.train_step(None, None)
        with pytest.raises(TypeError, match="serve_engine"):
            rt_s.init_state(None)

    def test_facade_bitwise_matches_legacy(self, ir_setup):
        """Runtime.train_step == hand-wired make_ir_state /
        make_ir_train_step + jit, bit for bit."""
        m, p, batch = ir_setup
        params = m.init(jax.random.PRNGKey(0))
        # both steps donate their state; fresh buffers per state so one
        # side's donation cannot delete the other's params
        fresh = lambda: jax.tree.map(lambda x: x.copy(), params)

        rt = Runtime(p, m, RuntimeConfig(mode="spectrain", lr=0.05,
                                         schedule="1f1b"))
        s_new = rt.init_state(fresh())

        s_old = ps.make_ir_state(m, fresh(), None, plan=p,
                                 mode="spectrain")
        step_old = jax.jit(ps.make_ir_train_step(
            m, plan=p, mode="spectrain", lr=0.05), donate_argnums=0)

        la, lb = [], []
        for _ in range(3):
            s_new, met_a = rt.train_step(s_new, batch)
            s_old, met_b = step_old(s_old, batch)
            la.append(float(met_a["loss"]))
            lb.append(float(met_b["loss"]))
        assert la == lb, (la, lb)
