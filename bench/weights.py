"""The benchmark's own inputs: weights and tokens, both from ``--seed``.

The weights are defined here, not by the program: the outer leaves and,
stacked over the layers, one layer's leaves as the configuration's
block file (``bench.cells.block``) lists them.  A leaf's values are
normal draws with std ``1/sqrt(rows)`` (``rows`` = the second-to-last
dimension of the leaf, per layer for stacked leaves) for every matrix,
embedding included, and ones for every norm scale.  Each leaf draws
from its own key, ``fold_in(weights_key, index)`` in sorted path order,
so one leaf can be drawn again alone and comes out bit for bit the
same.  The program is handed these weights through its own init path;
the reference draws them again itself.

Tokens are i.i.d. uniform below ``vocab_size``: a copy of the
``uniform`` stream of the program's ``SyntheticLM`` (same Philox key
and counter), so the reference reads the same rows the timed step read
without taking them from the program.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

from bench import cells

Path = Tuple[str, ...]


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` up to 2**62 (``--seed`` may
    pass 32 signed bits)."""
    import jax
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def outer_shapes(cfg: dict) -> Dict[Path, tuple]:
    d, V = cfg["hidden_size"], cfg["vocab_rows"]
    out: Dict[Path, tuple] = {("embed", "tok"): (V, d),
                              ("ln_f", "scale"): (d,)}
    if not cfg["tie_word_embeddings"]:
        out[("embed", "unembed")] = (d, V)
    return out


def leaf_table(cfg: dict, n_layers: int):
    """``[(group, path, shape)]`` in key order: ``group`` is "outer" or
    "layers" (whose shapes carry the leading ``n_layers``)."""
    layer = cells.block(cells.ROOT, cfg["block"]).layer_shapes(cfg)
    rows = [("outer", p, s) for p, s in outer_shapes(cfg).items()]
    rows += [("layers", p, (n_layers,) + s) for p, s in layer.items()]
    return sorted(rows, key=lambda r: (r[0],) + r[1])


def draw_leaf(key, index: int, path: Path, shape, dtype="float32"):
    """Leaf ``index`` of :func:`leaf_table` drawn from ``key``."""
    import jax
    import jax.numpy as jnp
    if path[-1] == "scale":
        return jnp.ones(shape, dtype)
    std = 1.0 / math.sqrt(shape[-2])
    k = jax.random.fold_in(key, index)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def weights_key(seed: int):
    import jax
    return jax.random.fold_in(seed_key(seed), 0)


def flat(tree: dict, prefix: Path = ()) -> Dict[Path, object]:
    """``{path: leaf}`` of a nested dict; :func:`nest` undoes it."""
    out: Dict[Path, object] = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out


def nest(flat: Dict[Path, object]) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v
    return out


def draw_all(cfg: dict, n_layers: int, key, layers_sharding=None):
    """``{"outer": {...}, "layers": {...}}`` with every layer leaf
    ``[n_layers, ...]``; call under ``jit``.  ``layers_sharding``
    places the layer stacks as they are drawn."""
    import jax
    groups: Dict[str, Dict[Path, object]] = {"outer": {}, "layers": {}}
    for i, (g, path, shape) in enumerate(leaf_table(cfg, n_layers)):
        v = draw_leaf(key, i, path, shape)
        if g == "layers" and layers_sharding is not None:
            v = jax.lax.with_sharding_constraint(v, layers_sharding)
        groups[g][path] = v
    return {g: nest(t) for g, t in groups.items()}


def tokens(cfg: dict, batch: int, seq: int, seed: int, step: int):
    """Step ``step``'s ``{"tokens", "targets"}`` int32 ``[batch, seq]``."""
    rng = np.random.Generator(np.random.Philox(key=seed + 1, counter=step))
    toks = rng.integers(0, cfg["vocab_size"], size=(batch, seq + 1),
                        dtype=np.int64)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "targets": toks[:, 1:].astype(np.int32)}
