"""The unified Runtime facade: one config, one entry point, both
workloads.

``RuntimeConfig`` is the single frozen bag of execution knobs that used
to sprawl across nine keyword arguments on ``make_ir_state`` /
``make_ir_train_step`` (mode, lr, gamma, clip, backend, execution,
mesh, verify); ``Runtime`` binds it to a planner artifact
and a model and exposes the two workloads:

    rt = Runtime(plan, model, RuntimeConfig(mode="spectrain", lr=2e-2))
    state = rt.init_state(model.init(key), batch_sds)
    state, metrics = rt.train_step(state, batch)       # PipelinePlan

    rt = Runtime(splan, model, RuntimeConfig(execution="mpmd"))
    results = rt.serve_step(params, requests)          # ServePlan

Dispatch is by plan type: a ``planner.PipelinePlan`` gives a training
runtime (streaming or IR-interpreted by ``plan.schedule``), a
``planner.ServePlan`` a serving runtime (``serve/engine.py``; the
``execution`` knob picks the scan/SPMD or shard_map/MPMD round).  The
legacy constructors stay importable for one release behind
``DeprecationWarning`` shims — see ``docs/SERVING.md`` for the
migration table.

``add_runtime_args`` / ``runtime_config_from_args`` are the one shared
argparse wiring ``launch/train.py`` and ``launch/serve.py`` both build
their config from.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Callable, Dict, Optional

import jax

from repro.core import pipeline_stream as ps
from repro.obs import phases

_SCHEDULES = ("stream",) + ps.IR_SCHEDULES


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Execution knobs for :class:`Runtime`, validated at construction.

    ``mode``       staleness-handling scheme (vanilla / pipedream /
                   spectrain); training only.
    ``schedule``   pipeline schedule the plan was compiled for —
                   ``"stream"`` (tick runtime) or an IR round schedule;
                   cross-checked against the plan at bind time.
                   ``None`` (default) adopts the bound plan's schedule
                   (serving plans carry none).
    ``backend``    IR round-body construction (scan / unrolled);
                   SPMD training only.
    ``execution``  SPMD (replicated weights, default) or MPMD
                   (stage-local weights over the pipe mesh axis) for
                   IR training rounds and serving rounds.
    ``verify``     statically verify compiled schedule artifacts
                   before execution (``planner/verify.py``).
    ``lr/gamma/clip/ticks_per_step``  optimizer and tick knobs the
                   training step consumes; serving ignores them.
    """
    mode: str = "spectrain"
    schedule: Optional[str] = None
    backend: str = "scan"
    execution: str = "spmd"
    verify: bool = True
    lr: float = 1e-2
    gamma: float = 0.9
    clip: Optional[float] = None
    ticks_per_step: int = 1

    def __post_init__(self):
        if self.mode not in ps.MODES:
            raise ValueError(f"unknown mode {self.mode!r}; "
                             f"known: {ps.MODES}")
        if self.schedule is not None and self.schedule not in _SCHEDULES:
            raise ValueError(f"unknown schedule {self.schedule!r}; "
                             f"known: {_SCHEDULES}")
        if self.backend not in ps.IR_BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"known: {ps.IR_BACKENDS}")
        if self.execution not in ps.EXECS:
            raise ValueError(f"unknown execution {self.execution!r}; "
                             f"known: {ps.EXECS}")
        if self.execution == "mpmd" and self.schedule == "stream":
            raise ValueError(
                "execution='mpmd' runs IR round schedules "
                f"({'/'.join(ps.IR_SCHEDULES)}) and serving rounds; "
                "the stream schedule is SPMD-only")
        if self.execution == "mpmd" and self.clip:
            raise ValueError(
                "execution='mpmd' does not support clip: the global "
                "norm's canonical-order reduction is not "
                "bit-reproducible on the packed stage layout")
        if self.ticks_per_step < 1:
            raise ValueError(f"ticks_per_step must be >= 1, got "
                             f"{self.ticks_per_step}")

    def replace(self, **kw) -> "RuntimeConfig":
        return dataclasses.replace(self, **kw)


class Runtime:
    """A planner artifact bound to a model under one
    :class:`RuntimeConfig`.

    Training (``plan`` is a :class:`~repro.planner.PipelinePlan`):
    :meth:`init_state` builds the schedule's train state from canonical
    init params and :meth:`train_step` executes one round/tick step,
    jitted with state donation; its first call records the step's
    argument shapes for :func:`repro.obs.phases.step_programs`.

    Serving (``plan`` is a :class:`~repro.planner.ServePlan`):
    :meth:`serve_engine` builds the continuous-batching
    :class:`~repro.serve.engine.ServeEngine` (``config.execution``
    picks the scan or mpmd round) and :meth:`serve_step` drives a
    request trace through it to completion.
    """

    def __init__(self, plan, model, config: Optional[RuntimeConfig]
                 = None, *, mesh=None, registry=None):
        from repro.planner.api import PipelinePlan, ServePlan
        if not isinstance(plan, (PipelinePlan, ServePlan)):
            raise TypeError(
                f"Runtime needs a planner PipelinePlan or ServePlan, "
                f"got {type(plan).__name__}")
        self.plan, self.model = plan, model
        self.config = config if config is not None else RuntimeConfig()
        self.mesh, self.registry = mesh, registry
        self.serving = isinstance(plan, ServePlan)
        if not self.serving:
            if self.config.schedule is not None \
                    and self.config.schedule != plan.schedule:
                raise ValueError(
                    f"RuntimeConfig.schedule={self.config.schedule!r} "
                    f"does not match the plan's schedule "
                    f"{plan.schedule!r}")
            if self.config.execution == "mpmd" \
                    and plan.schedule not in ps.IR_SCHEDULES:
                raise ValueError(
                    "execution='mpmd' runs IR round schedules "
                    f"({'/'.join(ps.IR_SCHEDULES)}); this plan's "
                    f"schedule is {plan.schedule!r}")
        self._step: Optional[Callable] = None
        self._step_args = None
        self._engine = None

    # ------------------------------------------------------------- training
    @property
    def _ir(self) -> bool:
        return (not self.serving
                and self.plan.schedule in ps.IR_SCHEDULES)

    def init_state(self, params, batch_sds=None) -> Dict[str, Any]:
        """Train state from canonical init ``params``
        (``model.init(key)``); ``batch_sds`` is required by the
        streaming schedule's activation rings."""
        if self.serving:
            raise TypeError("init_state is a training entry point; "
                            "this Runtime binds a ServePlan — use "
                            "serve_engine/serve_step")
        c = self.config
        if self._ir:
            return ps.make_ir_state(
                self.model, params, batch_sds, plan=self.plan,
                mode=c.mode, execution=c.execution, mesh=self.mesh,
                verify=c.verify)
        return ps.make_state(self.model, params, batch_sds,
                             mode=c.mode,
                             ticks_per_step=c.ticks_per_step,
                             plan=self.plan)

    def init(self, key, batch_sds=None) -> Dict[str, Any]:
        """Train state from a fresh ``model.init(key)``.

        Under ``execution="mpmd"`` the init and packing run as one
        jitted program whose outputs carry the stage-local shardings,
        and the flat layer stacks are drawn split over ``pipe``, so each
        device creates only its own share of the layers and the whole
        model never sits on one device; the bits are those of the
        unsplit jitted init.  Other executions build the state as
        ``init_state(model.init(key), batch_sds)``.
        """
        if not (self._ir and self.config.execution == "mpmd"):
            return self.init_state(self.model.init(key), batch_sds)
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.runtime import sharding as rsh

        mesh = ps._mpmd_mesh(self.mesh, self.plan.n_devices)
        rows = None
        if self.model.cfg.n_layers % self.plan.n_devices == 0:
            rows = NamedSharding(mesh, PartitionSpec("pipe"))

        def build(k):
            return self.init_state(self.model.init(k, rows), batch_sds)

        shardings = rsh.mpmd_state_shardings(mesh, jax.eval_shape(build, key))
        return jax.jit(build, out_shardings=shardings)(key)

    def train_step(self, state, batch):
        """One training step (round or tick group); built and jitted
        lazily on first call, donated state."""
        fn = self.step_fn()
        if self._step_args is None:
            self._step_args = phases.abstract((state, batch))
            phases.register(self)
        return fn(state, batch)

    def compiled_step(self):
        """The program :meth:`train_step` ran, compiled again from the
        shapes of its first call."""
        if self._step_args is None:
            raise ValueError("train_step has not run yet")
        return self.step_fn().lower(*self._step_args).compile()

    def step_fn(self) -> Callable:
        """The step :meth:`train_step` calls: jitted with donated state,
        so ``step_fn().lower(state_sds, batch_sds)`` compiles it for
        shapes alone."""
        if self.serving:
            raise TypeError("train_step is a training entry point; "
                            "this Runtime binds a ServePlan — use "
                            "serve_step")
        if self._step is None:
            c = self.config
            if self._ir:
                fn = ps.make_ir_train_step(
                    self.model, plan=self.plan, mode=c.mode, lr=c.lr,
                    gamma=c.gamma, clip=c.clip, backend=c.backend,
                    execution=c.execution, mesh=self.mesh,
                    verify=c.verify)
            else:
                fn = ps.make_train_step(
                    self.model, mode=c.mode, lr=c.lr, gamma=c.gamma,
                    clip=c.clip, ticks_per_step=c.ticks_per_step,
                    plan=self.plan)
            self._step = jax.jit(fn, donate_argnums=0)
        return self._step

    # -------------------------------------------------------------- serving
    def serve_engine(self, params):
        """The continuous-batching engine for ``params`` (built once
        and cached; ``config.execution`` picks the scan or mpmd
        serving round)."""
        if not self.serving:
            raise TypeError("serve_engine needs a ServePlan; this "
                            "Runtime binds a training PipelinePlan — "
                            "use init_state/train_step")
        if self._engine is None:
            from repro.serve import ServeEngine
            backend = "mpmd" if self.config.execution == "mpmd" \
                else "scan"
            self._engine = ServeEngine(
                self.model, params, self.plan, backend=backend,
                mesh=self.mesh, registry=self.registry,
                verify=self.config.verify)
        return self._engine

    def serve_step(self, params, requests, *,
                   max_rounds: Optional[int] = None) -> Dict[int, tuple]:
        """Drive ``requests`` (a trace of ``serve.Request``) through
        the engine to completion; returns ``{rid: emitted tokens}``."""
        return self.serve_engine(params).run(requests,
                                             max_rounds=max_rounds)


# ---------------------------------------------------------------- argparse
# the one shared flag wiring train.py and serve.py build their
# RuntimeConfig from (satellite: delete the duplicated per-launcher
# copies)


def add_runtime_args(ap, *, serving: bool = False) -> None:
    """Install the RuntimeConfig flags on ``ap``.  ``--exec`` stays as
    a hidden deprecated alias for ``--execution`` for one release."""
    if not serving:
        ap.add_argument("--mode", default="spectrain",
                        choices=("sync",) + ps.MODES)
        ap.add_argument("--schedule", default="stream",
                        choices=_SCHEDULES,
                        help="pipeline schedule: the streaming tick "
                             "runtime (default) or an IR-interpreted "
                             "round schedule (gpipe / 1f1b / 2bw / "
                             "interleaved)")
        ap.add_argument("--ir-backend", default="scan",
                        dest="ir_backend", choices=ps.IR_BACKENDS,
                        help="round-body construction for IR "
                             "schedules: 'scan' compiles a lax.scan "
                             "over the plan's event table (O(1) trace "
                             "size in the round's microbatch count), "
                             "'unrolled' inlines every event (the "
                             "reference oracle)")
        ap.add_argument("--lr", type=float, default=1e-2)
        ap.add_argument("--gamma", type=float, default=0.9)
        ap.add_argument("--clip", type=float, default=0.0)
    ap.add_argument("--execution", default=None, dest="execution",
                    choices=ps.EXECS,
                    help="execution backend: 'spmd' (default) runs "
                         "rounds as one replicated program, 'mpmd' "
                         "keeps stage weights/KV device-local "
                         "(shard_map over the pipe axis, payloads "
                         "cross stage cuts via ppermute); "
                         "bitwise-identical results, 1/S the "
                         "per-device weight memory (needs >= S "
                         "devices)")
    ap.add_argument("--exec", default=None, dest="exec_legacy",
                    choices=ps.EXECS, help=argparse.SUPPRESS)
    ap.add_argument("--no-verify", action="store_true",
                    dest="no_verify",
                    help="skip the static schedule verifier "
                         "(planner/verify.py) that runs by default at "
                         "step construction")


def runtime_config_from_args(args, **overrides) -> RuntimeConfig:
    """Build the :class:`RuntimeConfig` from parsed launcher flags —
    the single translation point from argv to config.  ``overrides``
    win over flags (launchers pin fields their workload fixes, e.g.
    serving has no --mode)."""
    execution = getattr(args, "execution", None)
    legacy = getattr(args, "exec_legacy", None)
    if legacy is not None:
        import warnings
        warnings.warn("--exec is deprecated; use --execution "
                      "(--exec will be removed next release)",
                      DeprecationWarning, stacklevel=2)
        if execution is not None and execution != legacy:
            raise SystemExit(f"--execution {execution} conflicts with "
                             f"legacy --exec {legacy}")
        execution = legacy
    kw: Dict[str, Any] = {
        "execution": execution or "spmd",
        "verify": not getattr(args, "no_verify", False),
    }
    if hasattr(args, "mode") and args.mode != "sync":
        kw["mode"] = args.mode
    if hasattr(args, "schedule"):
        kw["schedule"] = args.schedule
    if hasattr(args, "ir_backend"):
        kw["backend"] = args.ir_backend
    if hasattr(args, "lr"):
        kw["lr"] = args.lr
    if hasattr(args, "gamma"):
        kw["gamma"] = args.gamma
    if hasattr(args, "clip"):
        kw["clip"] = args.clip or None
    kw.update(overrides)
    return RuntimeConfig(**kw)
