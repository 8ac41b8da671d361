"""Phase scopes inside the compiled step programs (``repro.obs.phases``).

The parser is checked on a fixed HLO snippet; the scopes on every
training runtime's step program, compiled at smoke width on the CPU.
The MPMD programs need four devices: where the process has fewer, they
are compiled in a child process that forces four host devices.
"""
import glob
import json
import os
import subprocess
import sys

import jax
import pytest

from conftest import lm_batch, tiny_cfg
from repro.api import Runtime, RuntimeConfig
from repro.models import Model
from repro.obs import phases
from repro.planner import plan, synthetic_profile

# op_name metadata, kept apart so that the module's lines stay short
UPD = 'metadata={op_name="jit(step)/update/mul" stack_frame_id=1}'
FWD = 'metadata={op_name="jit(step)/while/body/forward/stage0/mul"}'
BWD = ('metadata={op_name="jit(step)/backward/stage1/transpose(jvp())/'
       'head/neg"}')
HLO = f"""HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {{
  %param_0 = f32[4]{{0}} parameter(0)
  %constant.1 = f32[] constant(2)
  %broadcast.1 = f32[4]{{0}} broadcast(%constant.1), dimensions={{}}
  ROOT %multiply.1 = f32[4]{{0}} multiply(%param_0, %broadcast.1), {UPD}
}}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {{
  %p = (s32[], f32[4]{{0}}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%p), index=0
  %gte.1 = f32[4]{{0}} get-tuple-element(%p), index=1
  %fusion.2 = f32[4]{{0}} fusion(%gte.1), calls=%fused_computation, {FWD}
  ROOT %tuple.1 = (s32[], f32[4]{{0}}) tuple(%gte.0, %fusion.2)
}}

%cond (q: (s32[], f32[4])) -> pred[] {{
  %q = (s32[], f32[4]{{0}}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%q), index=0
  %constant.2 = s32[] constant(3)
  ROOT %compare.1 = pred[] compare(%gte.2, %constant.2), direction=LT
}}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {{
  %Arg_0.1 = f32[4]{{0}} parameter(0)
  %fusion.1 = f32[4]{{0}} fusion(%Arg_0.1), calls=%fused_computation
  %constant.3 = s32[] constant(0)
  %tuple.0 = (s32[], f32[4]{{0}}) tuple(%constant.3, %fusion.1)
  %while.1 = (s32[], f32[4]{{0}}) while(%tuple.0), condition=%cond, body=%body
  %copy.1 = f32[4]{{0}} copy(%fusion.1)
  ROOT %negate.1 = f32[4]{{0}} negate(%copy.1), {BWD}
}}
"""


class TestParser:
    def test_fused_root_names_the_fusion(self):
        t = phases.op_phases(HLO)
        assert phases.module_name(HLO) == "jit_step"
        # fusion.1 carries no metadata of its own
        assert t["fusion.1"] == {"update"}
        assert t["multiply.1"] == {"update"}

    def test_nested_calls(self):
        t = phases.op_phases(HLO)
        assert t["fusion.2"] == {"forward", "update"}
        # while -> body -> fusion.2 -> fused_computation
        assert t["while.1"] == {"forward", "update"}

    def test_no_metadata_is_unscoped(self):
        t = phases.op_phases(HLO)
        assert t["copy.1"] == frozenset()
        assert t["compare.1"] == frozenset()
        assert phases.phase_of("jit(step)/while/body/closed_call") is None

    def test_innermost_phase_wins(self):
        assert phases.op_phases(HLO)["negate.1"] == {"head"}
        assert phases.phase_of("jit(f)/backward/stage0/forward/dot") \
            == "forward"
        with pytest.raises(ValueError, match="unknown phase"):
            phases.scope("stash")


# ------------------------------------------------------------ runtimes

RUNTIMES = {
    # id: (schedule, mode, execution)
    "stream-spectrain": ("stream", "spectrain", "spmd"),
    "stream-pipedream": ("stream", "pipedream", "spmd"),
    "gpipe": ("gpipe", "spectrain", "spmd"),
    "1f1b": ("1f1b", "spectrain", "spmd"),
    "2bw": ("2bw", "spectrain", "spmd"),
    "interleaved": ("interleaved", "spectrain", "spmd"),
    "1f1b-mpmd": ("1f1b", "spectrain", "mpmd"),
    "2bw-mpmd": ("2bw", "spectrain", "mpmd"),
}


def _runtime(schedule, mode, execution):
    """(runtime, state, batch, prediction lags) at smoke width; the MPMD
    runtimes take four stages, one per device."""
    S = 4 if execution == "mpmd" else 2
    v = 2 if schedule == "interleaved" else 1
    L = S * v
    cfg = tiny_cfg("granite-8b", n_layers=L, pipe=S)
    if schedule == "stream":
        p = plan(cfg, n_stages=S, schedule="stream", batch=4, seq=8)
        lags = set(p.s_fwd)
        B = 4
    else:
        p = plan(profile=synthetic_profile([1.0] * L), n_stages=S,
                 schedule=schedule, virtual_stages=v, n_microbatches=4)
        lags = {s for _k, _m, _q, s in p.round_program()}
        B = 4
    model = Model(cfg)
    rt = Runtime(p, model, RuntimeConfig(mode=mode, execution=execution))
    batch = lm_batch(jax.random.PRNGKey(1), cfg, batch=B, seq=8)
    state = rt.init(jax.random.PRNGKey(0), phases.abstract(batch))
    return rt, state, batch, lags


def _summary(name):
    """What the checks read of one runtime's compiled step."""
    rt, state, batch, lags = _runtime(*RUNTIMES[name])
    rt.train_step(state, batch)
    text = rt.compiled_step().as_text()
    table = phases.op_phases(text)
    # an instruction's own op_name path; a phase scope inside a
    # differentiated function would put forward under backward
    both = [n for n in phases._OP_NAME.findall(text)
            if {"forward", "backward"} <= set(n.split("/"))]
    return {"phases": sorted(frozenset().union(*table.values())),
            "both": both[:5], "lags": sorted(lags)}


@pytest.fixture(scope="module")
def mpmd_summaries():
    names = [n for n in RUNTIMES if n.endswith("-mpmd")]
    if jax.device_count() >= 4:
        return {n: _summary(n) for n in names}
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, __file__, *names], env=env,
                         cwd=os.path.dirname(here), capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", list(RUNTIMES))
def test_runtime_names_its_phases(name, request):
    schedule, mode, execution = RUNTIMES[name]
    if execution == "mpmd":
        got = request.getfixturevalue("mpmd_summaries")[name]
    else:
        got = _summary(name)
    have = set(got["phases"])
    assert {"forward", "head", "backward", "update"} <= have, have
    predicts = mode == "spectrain" and max(got["lags"]) > 0
    assert ("predict" in have) == predicts, (have, got["lags"])
    if execution == "mpmd":
        assert "transfer" in have
    assert got["both"] == []


def test_stream_last_stage_backward_applies_its_forward_vjp():
    """The last stage takes its vjp under ``forward/stage1`` and applies
    it under ``backward/stage1``: its backward recomputes no forward
    (no ``jvp()`` outside a transpose there) and it predicts nothing."""
    rt, state, batch, _ = _runtime("stream", "spectrain", "spmd")
    rt.train_step(state, batch)
    text = rt.compiled_step().as_text()
    names = phases._OP_NAME.findall(text)

    def under(prefix):
        return [n.split(prefix, 1)[1] for n in names if prefix in n]

    fwd, bwd = under("/forward/stage1/"), under("/backward/stage1/")
    assert any(n.startswith("jvp()/") for n in fwd)
    assert any(n.startswith("transpose(jvp())/") for n in bwd)
    assert not [n for n in bwd if n.startswith("jvp()")]
    assert not under("/predict/stage1/")
    # stage 0 keeps its remat'd backward
    assert any(n.startswith("jvp()/") for n in under("/backward/stage0/"))

    # an instruction's phases take in what fused into it; its own
    # scope is always among them
    table = phases.op_phases(text, phases.scope_of)
    seen = {"jvp": 0, "transpose": 0}
    for line in text.splitlines():
        m, n = phases._INSTR.match(line), phases._OP_NAME.search(line)
        if not (m and n):
            continue
        got = table[m.group(1)]
        if "/forward/stage1/jvp()/" in n.group(1):
            seen["jvp"] += 1
            assert phases.phase_of(n.group(1)) == "forward"
            assert "forward/stage1" in got
            assert not any(g.startswith("backward") for g in got), got
        elif "/backward/stage1/transpose(jvp())/" in n.group(1):
            seen["transpose"] += 1
            assert phases.phase_of(n.group(1)) == "backward"
            assert "backward/stage1" in got
    assert min(seen.values()) > 0, seen


def test_runtime_registers_on_first_step():
    rt, state, batch, _ = _runtime("1f1b", "spectrain", "spmd")
    with pytest.raises(ValueError, match="has not run"):
        rt.compiled_step()
    rt.train_step(state, batch)
    text = rt.compiled_step().as_text()
    progs = phases.step_programs()
    assert progs[phases.module_name(text)] == phases.op_phases(text)


def test_train_trace_dir_holds_the_steps_after_the_first(tmp_path):
    from jax.profiler import ProfileData
    from repro.launch import train
    assert train.main(["--smoke", "--pipe", "2", "--layers", "2",
                       "--steps", "3", "--batch", "4", "--seq", "16",
                       "--partitioner", "uniform", "--log-every", "3",
                       "--trace", str(tmp_path)]) == 0
    found = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(found) == 1
    assert glob.glob(str(tmp_path / "**" / "perfetto_trace.json.gz"),
                     recursive=True)
    steps = [dict(e.stats)["step_num"]
             for plane in ProfileData.from_file(found[0]).planes
             for line in plane.lines for e in line.events
             if e.name == "train_step"]
    assert sorted(steps) == [2, 3]


if __name__ == "__main__":
    print(json.dumps({n: _summary(n) for n in sys.argv[1:]}))
