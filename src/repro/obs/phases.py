"""Named phases of a training step, inside the compiled program.

Every training runtime wraps the call sites of one step in
``jax.named_scope`` phases (:data:`PHASES`), with a ``stage{k}`` scope
inside a phase wherever a loop runs over stages.  A scope is metadata
only: it names each HLO instruction's ``op_name`` (``jit(tick_fn)/
backward/stage1/dot_general``) and changes nothing the program computes.
The device trace (``jax.profiler``) names each operation that ran by its
HLO instruction; :func:`op_phases` maps that instruction to the phases
it computes, through the fused and called computations it runs.

No function that gets differentiated may contain a phase scope: the
transposed ops of a ``vjp`` carry the name stack of their forward ops
below the scope the ``vjp`` is applied in, so a ``forward`` scope inside
the differentiated function would name backward work ``forward``.

:func:`step_programs` gives the tables of every step program a
``repro.api.Runtime`` ran in this process, compiled again from the
shapes of its first call (a persistent-cache hit where the cache is on).
"""
from __future__ import annotations

import re
import weakref
from typing import Callable, Dict, FrozenSet, List, Optional

import jax

PHASES = ("predict", "forward", "head", "backward", "update", "transfer")

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                    r"false_computation|branch_computations|"
                    r"called_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_NAME = re.compile(r"%?([\w.\-]+)")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


def scope(phase: str):
    """The named scope of one of :data:`PHASES`."""
    if phase not in PHASES:
        raise ValueError(f"unknown phase {phase!r}; known: {PHASES}")
    return jax.named_scope(phase)


def stage(k: int):
    """The scope of stage (or chunk) ``k`` inside a phase."""
    return jax.named_scope(f"stage{k}")


def phase_of(op_name: str) -> Optional[str]:
    """The innermost phase component of an HLO ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in PHASES:
            return part
    return None


def scope_of(op_name: str) -> Optional[str]:
    """``backward/stage1``: the innermost phase and the stage scope
    inside it, where there is one."""
    parts = op_name.split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in PHASES:
            stage = next((p for p in parts[i + 1:]
                          if re.fullmatch(r"stage\d+", p)), None)
            return parts[i] + (f"/{stage}" if stage else "")
    return None


def op_phases(hlo_text: str, label: Callable[[str], Optional[str]]
              = phase_of) -> Dict[str, FrozenSet[str]]:
    """For each instruction of an optimized HLO module (``compiled.
    as_text()``), the phases of that instruction and of every
    instruction in the computations it calls, recursively.  An
    instruction whose ``op_name`` names no phase adds none.  ``label``
    maps an ``op_name`` to what it adds (``scope_of`` for phase and
    stage)."""
    comps: Dict[str, List[str]] = {}
    own: Dict[str, Optional[str]] = {}
    callees: Dict[str, List[str]] = {}
    cur: List[str] = []
    for line in hlo_text.splitlines():
        m = _HEADER.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        cur.append(name)
        n = _OP_NAME.search(line)
        own[name] = label(n.group(1)) if n else None
        callees[name] = [c for ref in _CALLS.findall(line)
                         for c in _NAME.findall(ref)]

    memo: Dict[str, FrozenSet[str]] = {}

    def of_comp(c: str) -> FrozenSet[str]:
        if c not in memo:
            memo[c] = frozenset().union(*(of_instr(i)
                                          for i in comps.get(c, ())))
        return memo[c]

    def of_instr(i: str) -> FrozenSet[str]:
        mine = {own[i]} if own[i] else set()
        return frozenset(mine.union(*(of_comp(c) for c in callees[i])))

    return {i: of_instr(i) for i in own}


def module_name(hlo_text: str) -> str:
    """``jit_tick_fn`` of ``HloModule jit_tick_fn, ...``: the name the
    device trace gives the program's executions."""
    m = _MODULE.search(hlo_text)
    if not m:
        raise ValueError("not an HLO module's text")
    return m.group(1)


# --------------------------------------------------------------- registry
# the runtimes whose train_step ran, held weakly: a runtime that is gone
# takes its program out of step_programs()
_RUNTIMES: List[weakref.ref] = []


def abstract(tree):
    """Shape and dtype of every array of ``tree``, as ``jit`` sees it,
    and the sharding of those committed to devices."""
    def leaf(x):
        aval = jax.typeof(x)
        sharding = x.sharding if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                    sharding=sharding,
                                    weak_type=aval.weak_type)
    return jax.tree.map(leaf, tree)


def register(runtime) -> None:
    """Record ``runtime`` (its ``compiled_step()`` gives its program)."""
    _RUNTIMES[:] = [r for r in _RUNTIMES if r() is not None]
    _RUNTIMES.append(weakref.ref(runtime))


def step_programs() -> Dict[str, Dict[str, FrozenSet[str]]]:
    """``{module name: op_phases(...)}`` for every step program a
    ``Runtime`` ran in this process; of two programs with one name, the
    later registered.  Compiles; never call it inside a timed window."""
    out = {}
    for ref in _RUNTIMES:
        rt = ref()
        if rt is not None:
            text = rt.compiled_step().as_text()
            out[module_name(text)] = op_phases(text)
    return out
