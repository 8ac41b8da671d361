"""The stream tick's last stage takes its vjp in its own forward.

A stage whose backward runs in the tick of its forward, at the weights
that forward read (``pipeline_stream.forward_reuse_stages``), keeps the
forward's vjp and applies it in the backward instead of running the
forward again from the stashed input.  A short reference tick below runs
the stream schedule the other way, re-linearizing every stage from the
stash; the runtime must give the same losses, weights and momentum.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import lm_batch, tiny_cfg
from repro.core import pipeline_stream
from repro.core import spectrain as st
from repro.launch import train
from repro.models import Model
from repro.optim import sgd
from repro.planner import plan


def reference_step(model, *, mode, lr, gamma=0.9, ticks_per_step=1,
                   fused_predict=False, bwd_dtype=None):
    """The stream schedule's step with every stage's backward
    re-linearized from the stashed input (no clip, no plan).  The last
    stage's forward reads its backward weights: the current ones, which
    Eq. 4 with s = 0 reproduces, cast to ``bwd_dtype`` where set."""
    S = model.n_stages
    s_fwd = [2 * (S - 1 - k) for k in range(S)]
    lag = [2 * (S - 1) - k for k in range(S)]
    gap = [2 * (S - 1 - k) for k in range(S)]
    R = max(lag + gap) + 1
    f32 = jnp.float32
    cdt = jnp.dtype(model.cfg.compute_dtype)

    def stage(w, x):
        return model.stage_apply(w, (x, jnp.zeros((), f32)))

    def bwd_weights(tree):
        if bwd_dtype is None:
            return tree
        return jax.tree.map(lambda p: p.astype(bwd_dtype), tree)

    def predicted(W, V):
        stages = tuple(st.predict_weights(w, v, lr, float(s))
                       for w, v, s in zip(W["stages"], V["stages"], s_fwd))
        return stages, st.predict_weights(W["outer"], V["outer"], lr,
                                          float(s_fwd[0]))

    def tick(state, batch):
        t = state["tick"]
        W, V = state["params"], state["momentum"]
        if fused_predict:
            fwd = list(state["pred"]["stages"])
            embed_w = state["pred"]["outer"]
        elif mode == "spectrain":
            fwd, embed_w = predicted(W, V)
            fwd = list(fwd)
        else:
            fwd, embed_w = list(W["stages"]), W["outer"]
        fwd[S - 1] = bwd_weights(W["stages"][S - 1])

        A = state["fwd_buf"].at[0].set(model.embed(embed_w, batch))
        out = jnp.stack([stage(fwd[k], A[k])[0] for k in range(S)])
        slot = jnp.mod(t, R)
        stash = state["stash_x"].at[:, slot].set(A)
        ring = jax.tree.map(lambda r, b: r.at[slot].set(b.astype(r.dtype)),
                            state["batch_ring"], batch)

        valid_head = (t >= S - 1).astype(f32)
        tgt = ring["targets"][jnp.mod(t - (S - 1), R)]
        loss, head_vjp = jax.vjp(
            lambda o, x: model.head_loss(o, x, tgt), W["outer"], out[S - 1])
        g_head, cot = head_vjp(valid_head)

        valid_b = (t - jnp.array(lag)) >= 0
        cots = state["bwd_buf"].at[S - 1].set(cot)
        cots = cots * valid_b[:, None, None, None].astype(cots.dtype)
        gW, gX = [], []
        for k in range(S):
            x_k = stash[k, jnp.mod(t - gap[k], R)]
            _, vjp_k = jax.vjp(stage, bwd_weights(W["stages"][k]), x_k)
            gw, gx = vjp_k((cots[k], valid_b[k].astype(f32)))
            gW.append(gw)
            gX.append(gx)
        old = jax.tree.map(lambda r: r[jnp.mod(t - lag[0], R)], ring)
        _, embed_vjp = jax.vjp(lambda o: model.embed(o, old), W["outer"])
        (g_embed,) = embed_vjp(gX[0] * valid_b[0].astype(gX[0].dtype))

        grads = {"outer": jax.tree.map(jnp.add, g_head, g_embed),
                 "stages": tuple(gW)}
        W2, V2 = sgd.update(W, sgd.MomentumState(V), grads, lr=lr,
                            gamma=gamma)
        new = {**state, "params": W2, "momentum": V2.v,
               "step": state["step"] + 1, "tick": t + 1,
               "fwd_buf": jnp.roll(out, 1, axis=0),
               "bwd_buf": jnp.roll(jnp.stack(gX), -1, axis=0),
               "stash_x": stash, "batch_ring": ring}
        if fused_predict:
            stages, outer = predicted(W2, V2.v)
            new["pred"] = jax.tree.map(lambda p: p.astype(cdt),
                                       {"stages": stages, "outer": outer})
        return new, {"loss": loss, "loss_valid": valid_head}

    if ticks_per_step == 1:
        return tick

    def step(state, batch):
        T = ticks_per_step
        mbs = jax.tree.map(
            lambda x: x.reshape((T, x.shape[0] // T) + x.shape[1:]), batch)
        state, mets = jax.lax.scan(tick, state, mbs)
        n = jnp.maximum(jnp.sum(mets["loss_valid"]), 1.0)
        return state, {"loss": jnp.sum(mets["loss"] * mets["loss_valid"]) / n,
                       "loss_valid": n}

    return step


def _setup(S, layers_per_stage, *, ticks_per_step=1, fused_predict=False,
           mode="spectrain"):
    cfg = tiny_cfg("granite-8b", n_layers=S * layers_per_stage, pipe=S)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = lm_batch(jax.random.PRNGKey(1), cfg, batch=4, seq=16)
    sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       batch)
    state = pipeline_stream.make_state(m, params, sds, mode=mode,
                                       ticks_per_step=ticks_per_step,
                                       fused_predict=fused_predict)
    return m, state, batch


def _assert_same(a, b):
    """Bitwise where the CPU gives it, else within 1e-6 relative."""
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if not np.array_equal(x, y):
            np.testing.assert_allclose(x, y, rtol=1e-6, atol=0)


# (mode, stages, layers per stage, ticks per step, fused_predict,
#  bwd_dtype)
CASES = [
    ("spectrain", 2, 1, 1, False, None),
    ("spectrain", 2, 2, 1, False, None),
    ("spectrain", 3, 1, 1, False, None),
    ("spectrain", 3, 2, 2, False, None),
    ("spectrain", 2, 1, 2, False, None),
    ("spectrain", 2, 2, 1, True, None),
    ("spectrain", 3, 1, 2, True, None),
    ("spectrain", 2, 2, 1, False, "bfloat16"),
    ("spectrain", 3, 1, 1, False, "bfloat16"),
    ("vanilla", 2, 2, 1, False, None),
    ("vanilla", 3, 1, 2, False, None),
    ("vanilla", 2, 1, 1, False, "bfloat16"),
]


@pytest.mark.parametrize(
    "case", CASES,
    ids=[f"{m}-S{S}-L{L}-T{T}" + ("-fused" if f else "") + (f"-{b}" if b
                                                              else "")
         for m, S, L, T, f, b in CASES])
def test_reuse_matches_relinearizing_from_the_stash(case):
    mode, S, L, T, fused, bwd = case
    m, state, batch = _setup(S, L, ticks_per_step=T, fused_predict=fused,
                             mode=mode)
    assert pipeline_stream.forward_reuse_stages(S, mode) == (S - 1,)
    kw = dict(mode=mode, lr=0.05, ticks_per_step=T, fused_predict=fused,
              bwd_dtype=bwd)
    step = jax.jit(pipeline_stream.make_train_step(m, **kw))
    ref = jax.jit(reference_step(m, **kw))
    got, want = state, state
    # past the warm-up: every stage's backward has run a few times
    for _ in range(2 * (S - 1) + 3):
        got, met_got = step(got, batch)
        want, met_want = ref(want, batch)
        _assert_same(met_got, met_want)
    assert float(met_got["loss_valid"]) > 0
    for key in ("params", "momentum") + (("pred",) if fused else ()):
        _assert_same(got[key], want[key])


@pytest.mark.parametrize("S", [2, 3, 4])
def test_reused_stages_by_mode_and_plan(S):
    p = plan(tiny_cfg("granite-8b", n_layers=S, pipe=S), n_stages=S,
             schedule="stream", batch=4, seq=8)
    for pl in (None, p):
        for mode in ("spectrain", "vanilla"):
            assert pipeline_stream.forward_reuse_stages(S, mode, pl) \
                == (S - 1,)
        assert pipeline_stream.forward_reuse_stages(S, "pipedream", pl) \
            == ()
    assert pipeline_stream.forward_reuse_stages(1, "spectrain") == ()


def _lowered(m, state, batch, mode):
    step = pipeline_stream.make_train_step(m, mode=mode, lr=0.05)
    return jax.jit(step).lower(state, batch)


@pytest.mark.parametrize("mode", ["spectrain", "vanilla", "pipedream"])
def test_only_pipedream_keeps_the_recompute(mode, monkeypatch):
    """The step with reuse turned off is the re-linearizing program.
    Pipedream's step is that program; the others' drop one stage's
    forward from the compiled count."""
    m, state, batch = _setup(2, 2, mode=mode)
    low = _lowered(m, state, batch, mode)
    monkeypatch.setattr(pipeline_stream, "forward_reuse_stages",
                        lambda *a, **k: ())
    off = _lowered(m, state, batch, mode)
    if mode == "pipedream":
        assert low.as_text() == off.as_text()
        return
    assert low.as_text() != off.as_text()
    flops = low.compile().cost_analysis()["flops"]
    flops_off = off.compile().cost_analysis()["flops"]
    assert flops < flops_off


@pytest.mark.parametrize("mode,want", [("spectrain", "(1,)"),
                                       ("vanilla", "(1,)"),
                                       ("pipedream", "()")])
def test_plan_lines_name_the_reused_stages(mode, want, capsys):
    train.setup(train.parse_args(
        ["--smoke", "--pipe", "2", "--layers", "2", "--mode", mode,
         "--batch", "4", "--seq", "16", "--partitioner", "uniform"]))
    lines = capsys.readouterr().out.splitlines()
    assert f"# stream: backward reuses the forward of stages {want}" \
        in lines
