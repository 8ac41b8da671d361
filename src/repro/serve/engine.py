"""The pipelined serving engine: schedule-IR rounds over paged KV.

One serving round executes a compiled artifact from
``planner/schedule_ir`` — the :class:`ServeTable` interpreted by a
``lax.scan``/``lax.switch`` loop (scan backend, SPMD) or the
:class:`ServeStreams` run tick-by-tick inside one ``shard_map`` over
the ``pipe`` mesh axis with both hidden payloads crossing the stage
cuts via ``ppermute`` (mpmd backend) — exactly the execution model of
the PR 5/PR 7 training interpreters, minus the backward half.

KV state is paged per stage: chunk ``q`` owns a buffer of
``n_pages + 1`` pages (the last is the trash page idle slots compute
into), each page one request's cache slice for that chunk's layers,
``page_seq`` positions deep.  A request occupies the *same* page index
on every stage (see ``scheduler``), which is what makes the elastic
repartition in :meth:`ServeEngine.restate` a concat-and-resplit along
the layer axis.

Both backends share the same per-chunk compute (:func:`_decode_chunk`
/ :func:`_prefill_chunk` over ``Model.stage_decode`` /
``Model.stage_prefill``), so their emitted tokens are
bitwise-identical by construction; prefill runs a whole prompt chunk
per dispatch (one XLA call per chunk, not per token), from a *fresh*
init page so a recycled page never leaks its previous request's state.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.planner import schedule_ir as sir
from repro.serve.scheduler import ContinuousBatcher, admissible

SERVE_BACKENDS = ("scan", "mpmd")


def _unsupported_arch(model, what: str) -> NotImplementedError:
    kind = "encoder-decoder" if model.cfg.is_encdec else "hybrid"
    return NotImplementedError(
        f"{what} does not support {kind} models ({model.cfg.name}): "
        f"their decode state is not a per-layer scan the stage split "
        f"can page; serve them with SimpleEngine (launch/serve.py "
        f"--engine simple)")


# ===========================================================================
# paged KV caches
# ===========================================================================


def _split_layer_tree(layers, sizes: Sequence[int]):
    """Slice a full-depth cache ``layers`` tree into per-chunk trees
    along the leading layer axis."""
    out, lo = [], 0
    for L in sizes:
        out.append(jax.tree.map(lambda a: a[lo:lo + L], layers))
        lo += L
    return out


def chunk_page_caches(model, sizes: Sequence[int], n_pages: int,
                      page_seq: int):
    """Build per-chunk paged KV buffers and the matching fresh init
    slices.

    Returns ``(caches, init_pages)``: ``caches[q]`` is a
    ``{"layers": ...}`` tree whose leaves are
    ``[n_pages + 1, sizes[q], 1, ...]`` — a leading page axis over
    chunk ``q``'s slice of ``Model.init_cache(1, page_seq)``, every
    page (including the trash page, index ``n_pages``) starting at the
    init state; ``init_pages[q]`` is the unpaged ``[sizes[q], 1, ...]``
    init slice prefill restarts from."""
    full = model.init_cache(1, page_seq, stage_sizes=tuple(sizes))
    slices = _split_layer_tree(full["layers"], sizes)
    caches = tuple(
        {"layers": jax.tree.map(
            lambda a: jnp.zeros((n_pages + 1,) + a.shape, a.dtype) + a,
            sl)}
        for sl in slices)
    init_pages = tuple({"layers": sl} for sl in slices)
    return caches, init_pages


def _decode_chunk(model, stage_params, cache_q, x, pos, pages):
    """One chunk of the decode wave: gather each request's page, run
    the stage's single-token decode (vmapped — attention needs a
    scalar position per request), scatter the pages back.

    x [R, 1, d], pos [R], pages [R] -> (y [R, 1, d], new cache)."""
    gathered = jax.tree.map(lambda a: a[pages], cache_q)

    def one(req_cache, xr, pr):
        y, nc = model.stage_decode(stage_params, req_cache, xr[None], pr)
        return y[0], nc

    ys, new = jax.vmap(one)(gathered, x, pos)
    new_cache = jax.tree.map(
        lambda leaf, n: leaf.at[pages].set(n.astype(leaf.dtype)),
        cache_q, new)
    return ys, new_cache


def _prefill_chunk(model, stage_params, init_page, cache_q, x_seq,
                   n_valid, page):
    """One chunk of a prefill lane: run the whole prompt through the
    stage in one masked scan, starting from the *fresh* init page (so
    a recycled page cannot leak its previous request's state), and
    scatter the result into the lane's page.

    x_seq [1, P, d] -> (y_seq [1, P, d], new cache)."""
    y_seq, new_page = model.stage_prefill(stage_params, init_page,
                                          x_seq, n_valid)
    new_cache = jax.tree.map(
        lambda leaf, n: leaf.at[page].set(n.astype(leaf.dtype)),
        cache_q, new_page)
    return y_seq, new_cache


# ===========================================================================
# scan backend: interpret the ServeTable (SPMD twin of the PR 5 loop)
# ===========================================================================


def make_scan_round(model, table: sir.ServeTable, init_pages):
    """Jittable round body interpreting ``table`` row by row:
    ``lax.scan`` over the dense rows, ``lax.switch`` into one arm per
    (opcode, chunk) branch, hidden states flowing through the two
    register-allocated slot pools.  Donate the caches argument when
    jitting."""
    C, F = table.n_chunks, table.max_prefill
    nd, npf = max(table.n_dec_slots, 1), max(table.n_pf_slots, 1)
    rows = jnp.asarray(np.asarray(table.rows))
    vocab = model.cfg.vocab_size
    dt = jnp.dtype(model.cfg.compute_dtype)

    def round_fn(chunks, outer, caches, dec_tokens, dec_pos, dec_pages,
                 pf_tokens, pf_len, pf_pages):
        R = dec_tokens.shape[0]
        P = pf_tokens.shape[1]
        d = model.cfg.d_model

        def with_chunk(caches, q, new_c):
            return tuple(new_c if i == q else c
                         for i, c in enumerate(caches))

        def mk_dec(q):
            def br(carry, row):
                dec_pool, pf_pool, caches, dec_next, pf_next = carry
                if q == 0:
                    x = model.decode_embed(outer, dec_tokens[:, None],
                                           dec_pos[:, None])
                else:
                    x = jax.lax.dynamic_index_in_dim(
                        dec_pool, row[sir.SCOL_A], 0, keepdims=False)
                y, new_c = _decode_chunk(model, chunks[q], caches[q],
                                         x, dec_pos, dec_pages)
                caches = with_chunk(caches, q, new_c)
                if q == C - 1:
                    dec_next = jnp.argmax(
                        model.logits(outer, y)[:, 0, :vocab],
                        -1).astype(jnp.int32)
                else:
                    dec_pool = jax.lax.dynamic_update_index_in_dim(
                        dec_pool, y.astype(dt), row[sir.SCOL_B], 0)
                return (dec_pool, pf_pool, caches, dec_next, pf_next)
            return br

        def mk_pf(q):
            def br(carry, row):
                dec_pool, pf_pool, caches, dec_next, pf_next = carry
                j = row[sir.SCOL_MB]
                n_valid = jax.lax.dynamic_index_in_dim(
                    pf_len, j, 0, keepdims=False)
                page = jax.lax.dynamic_index_in_dim(
                    pf_pages, j, 0, keepdims=False)
                if q == 0:
                    toks = jax.lax.dynamic_index_in_dim(
                        pf_tokens, j, 0, keepdims=False)
                    x = model.decode_embed(
                        outer, toks[None, :],
                        jnp.arange(P, dtype=jnp.int32)[None, :])
                else:
                    x = jax.lax.dynamic_index_in_dim(
                        pf_pool, row[sir.SCOL_A], 0, keepdims=False)
                y_seq, new_c = _prefill_chunk(
                    model, chunks[q], init_pages[q], caches[q], x,
                    n_valid, page)
                caches = with_chunk(caches, q, new_c)
                if q == C - 1:
                    idx = jnp.clip(n_valid - 1, 0, P - 1)
                    h = jax.lax.dynamic_slice_in_dim(y_seq, idx, 1, 1)
                    tok = jnp.argmax(
                        model.logits(outer, h)[0, 0, :vocab]
                    ).astype(jnp.int32)
                    pf_next = jax.lax.dynamic_update_index_in_dim(
                        pf_next, tok, j, 0)
                else:
                    pf_pool = jax.lax.dynamic_update_index_in_dim(
                        pf_pool, y_seq.astype(dt), row[sir.SCOL_B], 0)
                return (dec_pool, pf_pool, caches, dec_next, pf_next)
            return br

        arms = [mk_dec(q) if kind == sir.DECODE else mk_pf(q)
                for kind, q in table.branches]

        def step(carry, row):
            return jax.lax.switch(row[sir.SCOL_BRANCH], arms, carry,
                                  row), None

        carry = (jnp.zeros((nd, R, 1, d), dt),
                 jnp.zeros((npf, 1, P, d), dt),
                 caches,
                 jnp.zeros((R,), jnp.int32),
                 jnp.zeros((max(F, 1),), jnp.int32))
        carry, _ = jax.lax.scan(step, carry, rows)
        return carry[3], carry[4], carry[2]

    return round_fn


# ===========================================================================
# mpmd backend: run the ServeStreams inside shard_map (PR 7's twin)
# ===========================================================================


def pack_serve_caches(caches, sizes: Sequence[int]):
    """Per-chunk paged caches -> the dense stage-local layout: every
    leaf ``[n_pages + 1, L_q, ...]`` zero-padded to ``Lmax`` layers and
    stacked to ``[S, n_pages + 1, Lmax, ...]``; sharding dim 0 with
    ``PartitionSpec('pipe')`` pins chunk ``q``'s pages to device
    ``q``."""
    Lmax = max(sizes)

    def leaf(*xs):
        padded = []
        for x in xs:
            if x.shape[1] < Lmax:
                pad = [(0, 0)] * x.ndim
                pad[1] = (0, Lmax - x.shape[1])
                x = jnp.pad(x, pad)
            padded.append(x)
        return jnp.stack(padded, 0)

    return {"layers": jax.tree.map(leaf,
                                   *[c["layers"] for c in caches])}


def unpack_serve_caches(packed, sizes: Sequence[int]):
    """Inverse of :func:`pack_serve_caches` (padding layers dropped)."""
    return tuple(
        {"layers": jax.tree.map(lambda a: a[q, :, :sizes[q]],
                                packed["layers"])}
        for q in range(len(sizes)))


def make_mpmd_round(model, streams: sir.ServeStreams, init_pages,
                    sizes: Sequence[int], mesh):
    """Round body for the MPMD backend: one ``shard_map`` over the
    ``pipe`` axis; each device scans its own tick stream, both payload
    rings (decode [R, 1, d] and prefill [1, P, d] hiddens) run a
    ``ppermute`` every tick, incoming payloads park in the row's
    receive slot (-1 -> the trash slot).  Emitted tokens surface on
    the last device; index ``[S - 1]`` of the pipe-stacked outputs."""
    from jax.sharding import PartitionSpec as P_

    C, F, S = streams.n_chunks, streams.max_prefill, streams.n_devices
    nd, npf = streams.n_dec_slots, streams.n_pf_slots
    Lmax = max(sizes)
    rows = jnp.asarray(np.asarray(streams.rows))   # [T, S, SDN_COLS]
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    vocab = model.cfg.vocab_size
    dt = jnp.dtype(model.cfg.compute_dtype)
    # padded init pages: arm q slices its own [:sizes[q]] rows back out
    init_pad = tuple(
        {"layers": jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((Lmax - a.shape[0],) + a.shape[1:],
                              a.dtype)], 0) if a.shape[0] < Lmax else a,
            ip["layers"])}
        for ip in init_pages)

    def round_body(pp_l, outer, pc_l, rows_l, dec_tokens, dec_pos,
                   dec_pages, pf_tokens, pf_len, pf_pages):
        R = dec_tokens.shape[0]
        P = pf_tokens.shape[1]
        d = model.cfg.d_model
        zeros_d = lambda: jnp.zeros((R, 1, d), dt)
        zeros_p = lambda: jnp.zeros((1, P, d), dt)

        def chunk_of(q):
            return {"layers": jax.tree.map(
                lambda a: a[0, 0, :sizes[q]], pp_l["layers"])}

        def cache_of(pc, q):
            return {"layers": jax.tree.map(
                lambda a: a[0][:, :sizes[q]], pc["layers"])}

        def cache_set(pc, q, new_c):
            return {"layers": jax.tree.map(
                lambda a, n: a.at[0, :, :sizes[q]].set(
                    n.astype(a.dtype)),
                pc["layers"], new_c["layers"])}

        def mk_dec(q):
            def br(carry, row):
                dec_pool, pf_pool, pc, dec_next, pf_next = carry
                if q == 0:
                    x = model.decode_embed(outer, dec_tokens[:, None],
                                           dec_pos[:, None])
                else:
                    x = jax.lax.dynamic_index_in_dim(
                        dec_pool, row[sir.SDCOL_A], 0, keepdims=False)
                y, new_c = _decode_chunk(model, chunk_of(q),
                                         cache_of(pc, q), x, dec_pos,
                                         dec_pages)
                pc = cache_set(pc, q, new_c)
                if q == C - 1:
                    dec_next = jnp.argmax(
                        model.logits(outer, y)[:, 0, :vocab],
                        -1).astype(jnp.int32)
                    sd = zeros_d()
                else:
                    sd = y.astype(dt)
                return (dec_pool, pf_pool, pc, dec_next, pf_next), \
                    sd, zeros_p()
            return br

        def mk_pf(q):
            def br(carry, row):
                dec_pool, pf_pool, pc, dec_next, pf_next = carry
                j = row[sir.SDCOL_MB]
                n_valid = jax.lax.dynamic_index_in_dim(
                    pf_len, j, 0, keepdims=False)
                page = jax.lax.dynamic_index_in_dim(
                    pf_pages, j, 0, keepdims=False)
                if q == 0:
                    toks = jax.lax.dynamic_index_in_dim(
                        pf_tokens, j, 0, keepdims=False)
                    x = model.decode_embed(
                        outer, toks[None, :],
                        jnp.arange(P, dtype=jnp.int32)[None, :])
                else:
                    x = jax.lax.dynamic_index_in_dim(
                        pf_pool, row[sir.SDCOL_A], 0, keepdims=False)
                ipq = {"layers": jax.tree.map(
                    lambda a: a[:sizes[q]], init_pad[q]["layers"])}
                y_seq, new_c = _prefill_chunk(
                    model, chunk_of(q), ipq, cache_of(pc, q), x,
                    n_valid, page)
                pc = cache_set(pc, q, new_c)
                if q == C - 1:
                    idx = jnp.clip(n_valid - 1, 0, P - 1)
                    h = jax.lax.dynamic_slice_in_dim(y_seq, idx, 1, 1)
                    tok = jnp.argmax(
                        model.logits(outer, h)[0, 0, :vocab]
                    ).astype(jnp.int32)
                    pf_next = jax.lax.dynamic_update_index_in_dim(
                        pf_next, tok, j, 0)
                    sp = zeros_p()
                else:
                    sp = y_seq.astype(dt)
                return (dec_pool, pf_pool, pc, dec_next, pf_next), \
                    zeros_d(), sp
            return br

        arms = [mk_dec(q) if kind == sir.DECODE else mk_pf(q)
                for kind, q in streams.branches]
        arms.append(lambda carry, row: (carry, zeros_d(), zeros_p()))

        def tick(carry, row_t):
            row = row_t[0]
            carry, sd, sp = jax.lax.switch(
                row[sir.SDCOL_BRANCH], arms, carry, row)
            rd = jax.lax.ppermute(sd, "pipe", fwd_perm) if S > 1 else sd
            rp = jax.lax.ppermute(sp, "pipe", fwd_perm) if S > 1 else sp
            dec_pool, pf_pool, pc, dec_next, pf_next = carry
            dec_pool = jax.lax.dynamic_update_index_in_dim(
                dec_pool, rd, jnp.where(row[sir.SDCOL_RECV_D] >= 0,
                                        row[sir.SDCOL_RECV_D], nd), 0)
            pf_pool = jax.lax.dynamic_update_index_in_dim(
                pf_pool, rp, jnp.where(row[sir.SDCOL_RECV_P] >= 0,
                                       row[sir.SDCOL_RECV_P], npf), 0)
            return (dec_pool, pf_pool, pc, dec_next, pf_next), None

        carry = (jnp.zeros((nd + 1, R, 1, d), dt),
                 jnp.zeros((npf + 1, 1, P, d), dt),
                 pc_l,
                 jnp.zeros((R,), jnp.int32),
                 jnp.zeros((max(F, 1),), jnp.int32))
        (_dp, _pp, pc_l, dec_next, pf_next), _ = jax.lax.scan(
            tick, carry, rows_l)
        return dec_next[None], pf_next[None], pc_l

    run = jax.shard_map(
        round_body, mesh=mesh,
        in_specs=(P_(None, "pipe"), P_(), P_("pipe"),
                  P_(None, "pipe", None), P_(), P_(), P_(), P_(), P_(),
                  P_()),
        out_specs=(P_("pipe"), P_("pipe"), P_("pipe")),
        check_vma=False)

    def round_fn(packed_params, outer, packed_caches, dec_tokens,
                 dec_pos, dec_pages, pf_tokens, pf_len, pf_pages):
        return run(packed_params, outer, packed_caches, rows,
                   dec_tokens, dec_pos, dec_pages, pf_tokens, pf_len,
                   pf_pages)

    return round_fn


# ===========================================================================
# engines
# ===========================================================================


class ServeEngine:
    """Continuous-batching inference through the schedule-IR serving
    round.  ``backend`` picks the scan (SPMD) or mpmd (shard_map)
    execution of the *same* per-chunk compute; emitted tokens are
    bitwise-identical across backends for a given trace."""

    def __init__(self, model, params, splan, *, backend: str = "scan",
                 mesh=None, registry=None, verify: bool = True):
        if backend not in SERVE_BACKENDS:
            raise ValueError(f"unknown serve backend {backend!r}; "
                             f"choose from {SERVE_BACKENDS}")
        if model.cfg.is_encdec or model.hybrid:
            raise _unsupported_arch(model, "the pipelined ServeEngine")
        self.model, self.splan, self.backend = model, splan, backend
        self.registry = registry
        self.verify = verify
        if verify:
            splan.verify(device_streams=(backend == "mpmd"))
        self._outer = params["outer"]
        sizes = splan.stage_sizes
        self._chunks = model.partition_stage_params(
            params["stages"], sizes, n_chunks=len(sizes))
        self._mesh = mesh
        self._build(sizes)

    # ------------------------------------------------------------- lowering
    def _build(self, sizes: Tuple[int, ...]) -> None:
        splan, model = self.splan, self.model
        self._sizes = tuple(sizes)
        caches, init_pages = chunk_page_caches(
            model, sizes, splan.n_pages, splan.page_seq)
        if self.backend == "scan":
            table = splan.serve_table()
            fn = make_scan_round(model, table, init_pages)
            self._fn = jax.jit(fn, donate_argnums=(2,))
            self._caches = caches
            self._params_arg = self._chunks
        else:
            from repro.runtime import sharding as rsh
            from repro.models.model import pack_chunk_params
            streams = splan.serve_streams()
            S = streams.n_devices
            if self._mesh is None:
                self._mesh = rsh.mpmd_pipe_mesh(S)
            if "pipe" not in self._mesh.shape \
                    or self._mesh.shape["pipe"] != S:
                raise ValueError(
                    f"mpmd serving needs a mesh with a 'pipe' axis of "
                    f"size {S}, got {dict(self._mesh.shape)}")
            packed, _ = pack_chunk_params(self._chunks, S)
            fn = make_mpmd_round(model, streams, init_pages, sizes,
                                 self._mesh)
            self._fn = jax.jit(fn, donate_argnums=(2,))
            self._caches = pack_serve_caches(caches, sizes)
            self._params_arg = packed
        self._warm = False

    def _round(self, batch: Dict[str, np.ndarray]):
        args = (batch["dec_tokens"], batch["dec_pos"],
                batch["dec_pages"], batch["pf_tokens"], batch["pf_len"],
                batch["pf_pages"])
        dec_next, pf_next, self._caches = self._fn(
            self._params_arg, self._outer, self._caches, *args)
        if self.backend == "mpmd":
            S = self.splan.n_stages
            dec_next, pf_next = dec_next[S - 1], pf_next[S - 1]
        return dec_next, pf_next

    def _warm_up(self) -> float:
        """Compile the round on throwaway caches (the caches argument
        is donated) so steady-state latencies exclude XLA compilation
        — PR 7's compile-time exclusion, applied to serving."""
        splan = self.splan
        R, F = splan.n_slots, max(splan.max_prefill, 1)
        P = splan.prompt_budget
        zero = {"dec_tokens": np.zeros((R,), np.int32),
                "dec_pos": np.zeros((R,), np.int32),
                "dec_pages": np.full((R,), splan.n_pages, np.int32),
                "pf_tokens": np.zeros((F, P), np.int32),
                "pf_len": np.zeros((F,), np.int32),
                "pf_pages": np.full((F,), splan.n_pages, np.int32)}
        real = self._caches
        self._caches = jax.tree.map(jnp.array, real)   # throwaway copy
        t0 = time.time()
        out = self._round(zero)
        jax.block_until_ready(out[0])
        compile_s = time.time() - t0
        self._caches = real
        self._warm = True
        if self.registry is not None:
            self.registry.gauge("serve/compile_s").set(compile_s)
        return compile_s

    # ------------------------------------------------------------ execution
    def run(self, requests, *, max_rounds: Optional[int] = None
            ) -> Dict[int, tuple]:
        """Drive the trace to completion; returns ``{rid: tokens}``
        (rejected requests map to ``()``).  The scheduler event log of
        the last run is kept on ``self.last_events`` for
        ``verify_request_trace``."""
        if self.splan.max_prefill < 1 and requests:
            raise ValueError("max_prefill=0 can never admit a request")
        if not self._warm:
            self._warm_up()
        sched = ContinuousBatcher(self.splan, requests,
                                  registry=self.registry)
        limit = max_rounds if max_rounds is not None else (
            max((q.arrival for q in requests), default=0)
            + sum(max(q.gen_len, 1) for q in requests) + len(requests)
            + 8)
        hist = (self.registry.histogram("serve/token_ms")
                if self.registry is not None else None)
        r, n_tokens, busy_s = 0, 0, 0.0
        while sched.active:
            if r > limit:
                raise RuntimeError(
                    f"serving exceeded {limit} rounds with "
                    f"{len(sched.live)} live and {len(sched.queue)} "
                    f"queued requests — admission is stuck")
            batch = sched.poll(r)
            if not sched.n_round_tokens():
                nxt = sched.next_arrival()
                r = max(r + 1, nxt if nxt is not None else r + 1)
                continue
            t0 = time.time()
            dec_next, pf_next = self._round(batch)
            jax.block_until_ready(dec_next)
            dt_s = time.time() - t0
            toks = sched.n_round_tokens()
            busy_s += dt_s
            n_tokens += toks
            if hist is not None:
                for _ in range(toks):
                    hist.observe(dt_s * 1e3)
            sched.commit(r, dec_next, pf_next)
            r += 1
        self.last_events: List[Dict[str, Any]] = sched.events
        if self.registry is not None and busy_s > 0:
            self.registry.gauge("serve/decode_tok_per_s").set(
                n_tokens / busy_s)
        return dict(sched.results)

    # -------------------------------------------------------------- elastic
    def restate(self, new_splan) -> None:
        """Mid-run repartition onto ``new_splan``'s stage split: stage
        weights regroup by flat layer order and the paged KV buffers
        concat-and-resplit along the layer axis, so every request's
        state survives at the same page index and the emitted tokens
        are unchanged.  Page geometry must match."""
        old = self.splan
        for f in ("n_slots", "max_prefill", "prompt_budget", "n_pages",
                  "page_seq"):
            if getattr(old, f) != getattr(new_splan, f):
                raise ValueError(
                    f"restate cannot change {f} "
                    f"({getattr(old, f)} -> {getattr(new_splan, f)}): "
                    f"page geometry is carried state")
        if self.backend == "mpmd":
            chunk_caches = unpack_serve_caches(self._caches,
                                              self._sizes)
        else:
            chunk_caches = self._caches
        full = jax.tree.map(lambda *xs: jnp.concatenate(xs, 1),
                            *[c["layers"] for c in chunk_caches])
        # pull off the old mesh: the new round fn may shard over a
        # different device set, and donated inputs committed to the old
        # one would be rejected at the jit boundary
        full = jax.device_get(full)
        new_sizes = new_splan.stage_sizes
        self._chunks = self.model.partition_stage_params(
            self._chunks, new_sizes, n_chunks=len(new_sizes))
        self.splan = new_splan
        if self.verify:
            new_splan.verify(device_streams=(self.backend == "mpmd"))
        self._mesh = None if self.backend == "mpmd" else self._mesh
        self._build(new_sizes)
        # overwrite the freshly-initialized pages with the carried
        # state (layer axis is 1 — axis 0 is the page axis)
        carried, lo = [], 0
        for L in new_sizes:
            carried.append({"layers": jax.tree.map(
                lambda a, lo=lo, L=L: a[:, lo:lo + L], full)})
            lo += L
        carried = tuple(carried)
        if self.backend == "mpmd":
            self._caches = pack_serve_caches(carried, new_sizes)
        else:
            self._caches = carried
        if self.registry is not None:
            self.registry.emit("serve_restate",
                               sizes=list(new_sizes),
                               backend=self.backend)


class SimpleEngine:
    """Whole-model reference engine: each request prefills and decodes
    independently through ``Model.decode_step`` on a fresh cache.  The
    golden reference the pipelined engine is tested against, and the
    serving fallback for hybrid/enc-dec archs ``stage_decode`` gates
    out.  Applies the same admission budgets, so results line up
    request-for-request.

    Prefill consumes the *whole* prompt in one jitted call — a masked
    ``lax.scan`` of ``decode_step`` over the padded prompt buffer
    (bitwise the old token-by-token stepping, minus per-token
    dispatch), compiled once for all prompt lengths."""

    def __init__(self, model, params, splan, *, registry=None):
        self.model, self.params, self.splan = model, params, splan
        self.registry = registry
        self._decode = jax.jit(model.decode_step)
        self._prefill = jax.jit(self._prefill_fn)
        self._warm = False

    def _prefill_fn(self, params, cache, toks, n_valid):
        """toks [1, P] zero-padded -> (last valid logits, filled
        cache); positions >= n_valid leave cache and logits
        untouched."""
        model = self.model
        tok0 = jax.lax.dynamic_slice_in_dim(toks, 0, 1, 1)
        logits, cache = model.decode_step(params, cache, tok0,
                                          jnp.asarray(0, jnp.int32))

        def body(carry, i):
            cache, logits = carry
            ti = jax.lax.dynamic_slice_in_dim(toks, i, 1, 1)
            lg, nc = model.decode_step(params, cache, ti, i)
            keep = i < n_valid
            cache = jax.tree.map(
                lambda o, n: jnp.where(keep, n.astype(o.dtype), o),
                cache, nc)
            logits = jnp.where(keep, lg.astype(logits.dtype), logits)
            return (cache, logits), None

        (cache, logits), _ = jax.lax.scan(
            body, (cache, logits),
            jnp.arange(1, toks.shape[1], dtype=jnp.int32))
        return logits, cache

    def _warm_up(self) -> None:
        """Compile prefill + decode on a throwaway cache so reported
        latencies exclude XLA compilation."""
        model, splan = self.model, self.splan
        t0 = time.time()
        warm = model.init_cache(1, splan.page_seq)
        toks = jnp.zeros((1, splan.prompt_budget), jnp.int32)
        logits, warm = self._prefill(self.params, warm, toks,
                                     jnp.asarray(1, jnp.int32))
        logits, warm = self._decode(self.params, warm, toks[:, :1],
                                    jnp.asarray(1, jnp.int32))
        jax.block_until_ready(logits)
        del warm
        self._warm = True
        if self.registry is not None:
            self.registry.gauge("serve/compile_s").set(
                time.time() - t0)

    def run(self, requests, *, max_rounds: Optional[int] = None
            ) -> Dict[int, tuple]:
        model, params, splan = self.model, self.params, self.splan
        vocab = model.cfg.vocab_size
        P = splan.prompt_budget
        if not self._warm:
            self._warm_up()
        hist = (self.registry.histogram("serve/token_ms")
                if self.registry is not None else None)
        results: Dict[int, tuple] = {}
        for req in sorted(requests, key=lambda q: (q.arrival, q.rid)):
            if not admissible(req, splan):
                results[req.rid] = ()
                continue
            cache = model.init_cache(1, splan.page_seq)
            toks_in = np.zeros((1, P), np.int32)
            toks_in[0, :len(req.prompt)] = req.prompt
            t0 = time.time()
            logits, cache = self._prefill(
                params, cache, jnp.asarray(toks_in),
                jnp.asarray(len(req.prompt), jnp.int32))
            jax.block_until_ready(logits)
            if hist is not None:
                hist.observe((time.time() - t0) * 1e3)
            toks = [int(jnp.argmax(logits[0, -1, :vocab]))]
            pos = len(req.prompt)
            while len(toks) < req.gen_len:
                t0 = time.time()
                logits, cache = self._decode(
                    params, cache,
                    jnp.asarray([[toks[-1]]], jnp.int32),
                    jnp.asarray(pos, jnp.int32))
                toks.append(int(jnp.argmax(logits[0, -1, :vocab])))
                pos += 1
                if hist is not None:
                    hist.observe((time.time() - t0) * 1e3)
            results[req.rid] = tuple(toks)
            if self.registry is not None:
                self.registry.emit("serve_request", rid=req.rid,
                                   prompt_len=len(req.prompt),
                                   gen=req.gen_len)
        return results
