"""Plain float32 training of a cell's job: the steps the timed path
takes first, followed in the reference's own code.

Two schedules are modelled, as the SpecTrain paper and the repo define
them, with momentum SGD (``v = gamma v + (1 - gamma) g``,
``W -= lr v``):

* a flush round (``1f1b``, ``gpipe``): the step's gradient is the mean
  over the whole batch at the step's weights; no prediction (the lag of
  every read is 0).
* ``stream``: one tick per step.  Stage ``k`` runs forward on what stage
  ``k-1`` produced a tick before, with weights predicted
  ``s = 2(S-1-k)`` updates ahead (``W - s lr v``, the embedding with
  stage 0's ``s``), and backward ``2(S-1)-k`` ticks after the batch's
  injection, at the current weights, from the input it stashed; the head
  scores stage ``S-1``'s output at the current weights.  Every tick
  updates every leaf, with a zero gradient where nothing is valid yet.

Stage ``k`` lives on ``devices[k]`` (one device for every stage where
one is given); activations and cotangents move between them by
``jax.device_put``.  Rows are taken a few at a time, so the full batch's
activations never sit on a device at once.  Nothing here imports the
program; the weights and tokens are drawn again by ``bench.weights``,
and the layer and the embedding's and head's scales come from the
configuration's block file (``bench.cells.block``).

``lowp`` computes every matrix product from operands rounded to float8
(the control).  ``fault`` plants one of the faults a timed step can
have: ``"half_batch"`` (the second half of the rows replaced by the
first, so the mean runs over half the batch), ``"no_exchange"`` (what
crosses a stage cut arrives as zeros) or ``"no_prediction"`` (the
stream schedule's forward at the current weights, ``s = 0``).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import cells
from bench import weights as wt
from bench.reference import common as c

FAULTS = ("half_batch", "no_exchange", "no_prediction")


def check_steps(job: dict) -> int:
    """Steps the reference follows: three full steps, after the stream
    schedule's warm-up ticks in which some stage has no gradient yet."""
    return warmup(job) + 3


def warmup(job: dict) -> int:
    return 2 * (job["stages"] - 1) if job["schedule"] == "stream" else 0



class Reference:
    def __init__(self, cfg: dict, job: dict, seed: int, devices,
                 lowp: bool = False, fault: Optional[str] = None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
        self.cfg, self.job, self.seed = cfg, job, seed
        self.lowp, self.fault = lowp, fault
        self.S = job["stages"]
        self.Lk = job["layers_per_stage"]
        self.L = self.S * self.Lk
        self.lr, self.gamma = job["lr"], job["gamma"]
        self.tied = cfg["tie_word_embeddings"]
        self.devs = [devices[k % len(devices)] for k in range(self.S)]
        self.dev0, self.devh = self.devs[0], self.devs[-1]
        self._dev = {dv.id: dv for dv in self.devs}
        self.rows = max(1, 2048 // job["seq"])
        blk = cells.block(cells.ROOT, cfg["block"])
        layer = blk.layer
        emb_scale, logit_scale = blk.embed_scale(cfg), blk.logit_scale(cfg)
        eps = cfg["rms_norm_eps"]
        d = cfg["hidden_size"]

        def stage(p, x):
            for i in range(jax.tree.leaves(p)[0].shape[0]):
                x = jax.checkpoint(
                    lambda pi, xi: layer(cfg, pi, xi, lowp))(
                    jax.tree.map(lambda a: a[i], p), x)
            return x

        def stage_bwd(p, x, cot):
            _, f = jax.vjp(stage, p, x)
            return f(cot)

        def head(ln_f, w_out, x, tgt, weight):
            def f(ln_f, w_out, x):
                return c.head_loss(x, ln_f, w_out, tgt, eps, logit_scale,
                                   lowp)
            loss, g = jax.value_and_grad(f, (0, 1, 2))(ln_f, w_out, x)
            return loss, jax.tree.map(lambda a: a * weight, g)

        def embed_grad(tokens, cot, n_rows):
            g = jnp.zeros((n_rows, d), jnp.float32)
            return g.at[tokens.reshape(-1)].add(
                cot.reshape(-1, d) * emb_scale)

        self._fwd = jax.jit(stage)
        self._bwd = jax.jit(stage_bwd)
        self._head = jax.jit(head)
        self._embed = jax.jit(lambda tok, t: c.embed(tok, t, emb_scale))
        self._embed_grad = jax.jit(embed_grad, static_argnums=2)
        self._draw()

    # ----------------------------------------------------------- weights
    def _draw(self):
        """W and v: stage k's layers on its device; the outer leaves on
        the head's device, the embedding also on stage 0's."""
        key = wt.weights_key(self.seed)
        self.W: Dict = {"stages": [dict() for _ in range(self.S)],
                        "outer": {}}
        for i, (g, path, shape) in enumerate(wt.leaf_table(self.cfg,
                                                           self.L)):
            for dev, put in self._homes(g, path):
                full = _draw_on(dev, key, i, path, shape)
                if g == "layers":
                    k = put
                    lo = k * self.Lk
                    self.W["stages"][k][path] = full[lo:lo + self.Lk]
                else:
                    self.W["outer"].setdefault(path, {})[dev.id] = full
                del full
        self.V = jax.tree.map(jnp.zeros_like, self.W)

    def _homes(self, group, path):
        if group == "layers":
            return [(self.devs[k], k) for k in range(self.S)]
        devs = [self.devh]
        if path == ("embed", "tok") and self.dev0 != self.devh:
            devs = [self.dev0] + ([self.devh] if self.tied else [])
        return [(d, None) for d in devs]

    def _outer(self, path, dev):
        return self.W["outer"][path][dev.id]

    def _w_out(self, W):
        if self.tied:
            return W["outer"][("embed", "tok")][self.devh.id].T
        return W["outer"][("embed", "unembed")][self.devh.id]

    # -------------------------------------------------------------- data
    def _batch(self, step: int):
        b = wt.tokens(self.cfg, self.job["batch"], self.job["seq"],
                      self.seed, step)
        if self.fault == "half_batch":
            h = self.job["batch"] // 2
            b = {k: np.concatenate([v[:h], v[:h]]) for k, v in b.items()}
        return b

    def _blocks(self):
        B = self.job["batch"]
        return [(lo, min(lo + self.rows, B)) for lo in range(0, B, self.rows)]

    def _cut(self, x, dev):
        """What crosses a stage cut (activation or cotangent)."""
        x = jax.device_put(x, dev)
        return jnp.zeros_like(x) if self.fault == "no_exchange" else x

    # ------------------------------------------------------------ update
    def _update(self, grads):
        """``grads``: {"stages": [ {path: g} ], "outer": {path: g}}
        (missing = zero) -- every leaf moves every step."""
        def upd(w, v, g):
            return _UPD(w, v, g, self.lr, self.gamma) if g is not None \
                else _DECAY(w, v, self.lr, self.gamma)

        for k in range(self.S):
            for path in self.W["stages"][k]:
                g = grads["stages"][k].get(path)
                w, v = upd(self.W["stages"][k][path],
                           self.V["stages"][k][path], g)
                self.W["stages"][k][path] = w
                self.V["stages"][k][path] = v
        for path, copies in self.W["outer"].items():
            g = grads["outer"].get(path)
            for i in copies:
                gd = None if g is None else jax.device_put(g, self._dev[i])
                w, v = upd(copies[i], self.V["outer"][path][i], gd)
                copies[i] = w
                self.V["outer"][path][i] = v

    @staticmethod
    def _acc(tree: dict, path, g):
        tree[path] = g if path not in tree else tree[path] + g

    def _stage_params(self, W, k):
        return wt.nest(W["stages"][k])

    def _head_grads(self, W, x, tgt, weight, grads):
        ln = W["outer"][("ln_f", "scale")][self.devh.id]
        loss, (g_ln, g_out, cot) = self._head(ln, self._w_out(W), x,
                                              jnp.asarray(tgt), weight)
        self._acc(grads["outer"], ("ln_f", "scale"), g_ln)
        if self.tied:
            self._acc(grads["outer"], ("embed", "tok"), g_out.T)
        else:
            self._acc(grads["outer"], ("embed", "unembed"), g_out)
        return loss, cot

    def _embed_grads(self, tokens, cot, grads):
        g = self._embed_grad(jax.device_put(tokens, self.dev0),
                             jax.device_put(cot, self.dev0),
                             self.cfg["vocab_rows"])
        self._acc(grads["outer"], ("embed", "tok"), jax.device_put(
            g, self.devh))

    # ------------------------------------------------------------- steps
    def round_step(self, step: int) -> float:
        batch = self._batch(step)
        B = self.job["batch"]
        grads = {"stages": [dict() for _ in range(self.S)], "outer": {}}
        loss = 0.0
        tok0 = self._outer(("embed", "tok"), self.dev0)
        for lo, hi in self._blocks():
            w = (hi - lo) / B
            x = self._embed(tok0, jax.device_put(batch["tokens"][lo:hi],
                                                 self.dev0))
            xs = []
            for k in range(self.S):
                x = self._cut(x, self.devs[k]) if k else x
                xs.append(x)
                x = self._fwd(self._stage_params(self.W, k), x)
            x = jax.device_put(x, self.devh)
            lb, cot = self._head_grads(self.W, x, batch["targets"][lo:hi],
                                       w, grads)
            loss += float(lb) * w
            for k in reversed(range(self.S)):
                if k < self.S - 1:
                    cot = self._cut(cot, self.devs[k])
                gw, cot = self._bwd(self._stage_params(self.W, k), xs[k],
                                    jax.device_put(cot, self.devs[k]))
                for path, g in wt.flat(gw).items():
                    self._acc(grads["stages"][k], path, g)
            self._embed_grads(batch["tokens"][lo:hi], cot, grads)
        self._update(grads)
        return loss

    def stream_step(self, t: int) -> Optional[float]:
        S, lr = self.S, self.lr
        if t == 0:
            self._fwd_buf = [None] * S
            self._bwd_buf = [None] * S
            self._stash: Dict[int, List] = {}
            self._batches: Dict[int, dict] = {}
        batch = self._batch(t)
        self._batches[t] = batch
        s = [0 if self.fault == "no_prediction" else 2 * (S - 1 - k)
             for k in range(S)]
        lag = [2 * (S - 1) - k for k in range(S)]
        gap = [2 * (S - 1 - k) for k in range(S)]
        blocks = self._blocks()

        inputs, outs = [], []
        for k in range(S):
            # predicted just in time: one stage's copy at a time
            Wf = wt.nest({p: w - s[k] * lr * self.V["stages"][k][p]
                          for p, w in self.W["stages"][k].items()})
            if k == 0:
                tok_f = (self._outer(("embed", "tok"), self.dev0) - s[0] * lr
                         * self.V["outer"][("embed", "tok")][self.dev0.id])
                xin = [self._embed(tok_f, jax.device_put(
                    batch["tokens"][lo:hi], self.dev0)) for lo, hi in blocks]
                del tok_f
            elif self._fwd_buf[k] is None:
                shape = (self.rows, self.job["seq"], self.cfg["hidden_size"])
                xin = [jax.device_put(jnp.zeros((hi - lo,) + shape[1:]),
                                      self.devs[k]) for lo, hi in blocks]
            else:
                xin = [self._cut(x, self.devs[k]) for x in self._fwd_buf[k]]
            inputs.append(xin)
            outs.append([self._fwd(Wf, x) for x in xin])
            del Wf
        self._stash[t] = inputs

        grads = {"stages": [dict() for _ in range(S)], "outer": {}}
        loss = None
        cots = None
        if t >= S - 1:
            tgt = self._batches[t - (S - 1)]["targets"]
            B = self.job["batch"]
            loss, cots = 0.0, []
            for (lo, hi), x in zip(blocks, outs[S - 1]):
                lb, cot = self._head_grads(self.W, jax.device_put(
                    x, self.devh), tgt[lo:hi], (hi - lo) / B, grads)
                loss += float(lb) * (hi - lo) / B
                cots.append(cot)
        gX: List = [None] * S
        for k in reversed(range(S)):
            if t - lag[k] < 0:
                continue
            if k == S - 1:
                cin = cots
            else:
                cin = [self._cut(cc, self.devs[k]) for cc in self._bwd_buf[k]]
            p = self._stage_params(self.W, k)
            gx_k = []
            for x, cc in zip(self._stash[t - gap[k]][k], cin):
                gw, gx = self._bwd(p, x, jax.device_put(cc, self.devs[k]))
                for path, g in wt.flat(gw).items():
                    self._acc(grads["stages"][k], path, g)
                gx_k.append(gx)
            gX[k] = gx_k
        if t - lag[0] >= 0:
            old = self._batches[t - lag[0]]["tokens"]
            for (lo, hi), gx in zip(blocks, gX[0]):
                self._embed_grads(old[lo:hi], gx, grads)
        self._fwd_buf = [None] + outs[:-1]
        self._bwd_buf = gX[1:] + [None]
        for old_t in [u for u in self._stash if u <= t - max(gap)]:
            del self._stash[old_t]
        self._update(grads)
        return loss

    # ---------------------------------------------------------- readings
    def run(self, n_steps: int, g_step: int) -> dict:
        """Follow ``n_steps`` steps; the momentum's per-leaf norms after
        step ``g_step`` and the weights' change after the last."""
        step = (self.stream_step if self.job["schedule"] == "stream"
                else self.round_step)
        losses, mom = [], None
        with jax.default_matmul_precision("highest"):
            for t in range(n_steps):
                losses.append(step(t))
                if t + 1 == g_step:
                    mom = self.leaf_norms(self.V)
            change = self.change_norms()
        return {"losses": losses, "momentum": mom, "change": change}

    def leaf_norms(self, tree) -> Dict[str, float]:
        out = {}
        for path, copies in tree["outer"].items():
            out["outer/" + "/".join(path)] = float(
                jnp.linalg.norm(next(iter(copies.values())).ravel()))
        for k in range(self.S):
            for path, a in tree["stages"][k].items():
                n = np.asarray(jnp.sqrt(jnp.sum(
                    jnp.square(a.reshape(a.shape[0], -1)), 1)))
                for j, v in enumerate(n):
                    out[f"layers/{'/'.join(path)}/{k * self.Lk + j}"] = \
                        float(v)
        return out

    def change_norms(self) -> Dict[str, float]:
        """Per-leaf ``|W_now - W_0|``, ``W_0`` drawn again leaf by
        leaf."""
        key = wt.weights_key(self.seed)
        delta = {"stages": [dict() for _ in range(self.S)], "outer": {}}
        for i, (g, path, shape) in enumerate(wt.leaf_table(self.cfg,
                                                           self.L)):
            if g == "outer":
                i0, w = next(iter(self.W["outer"][path].items()))
                w0 = _draw_on(self._dev[i0], key, i, path, shape)
                delta["outer"][path] = {i0: w - w0}
            else:
                for k in range(self.S):
                    w0 = _draw_on(self.devs[k], key, i, path, shape)
                    lo = k * self.Lk
                    delta["stages"][k][path] = (
                        self.W["stages"][k][path] - w0[lo:lo + self.Lk])
            del w0
        return self.leaf_norms(delta)


_DRAW = {}
_UPD = jax.jit(lambda w, v, g, lr, gm: (w - lr * (gm * v + (1 - gm) * g),
                                        gm * v + (1 - gm) * g))
_DECAY = jax.jit(lambda w, v, lr, gm: (w - lr * gm * v, gm * v))


def _draw_on(dev, key, i, path, shape):
    k = (tuple(shape), path[-1] == "scale")
    if k not in _DRAW:
        _DRAW[k] = jax.jit(
            lambda key, i: wt.draw_leaf(key, i, path, shape))
    return _DRAW[k](jax.device_put(key, dev), i)
