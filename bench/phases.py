"""Shares of the traced window by step phase.

The training runtimes name their work with the phases of
``repro.obs.phases`` (``predict``, ``forward``, ``head``, ``backward``,
``update``, ``transfer``).  Each operation in the window is matched, by
time, to the execution of its program on the device's ``XLA Modules``
line, and then, by its instruction name, to that program's table
(``repro.obs.phases.step_programs()``): the set of phases its
instructions compute.  An operation is then

* of one phase, where its set holds that phase alone;
* mixed, where its set holds two or more (a fusion of the optimizer
  update with a weight-gradient matmul, say);
* unscoped, where its set is empty.

Where operations nest (a ``while`` and the operations of its body),
each instant goes to the innermost one, so the classes add up to the
busy time.  Each share is a percentage of the window, as a mean over
the cell's chips.  A program of the parent commit names no phases and
the readers return nothing there.
"""
from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace as tr

# repro.obs.phases.PHASES, written again: the benchmark also runs on
# commits that lack that module
PHASES = ("predict", "forward", "head", "backward", "update", "transfer")
CLASSES = PHASES + ("mixed", "unscoped")
_RUN_ID = re.compile(r"\(\d+\)$")


def module_key(trace_name: str) -> str:
    """``jit_tick_fn`` of the trace's ``jit_tick_fn(1437...)``."""
    return _RUN_ID.sub("", trace_name)


def classify(phase_set: Iterable[str]) -> str:
    s = set(phase_set)
    if not s:
        return "unscoped"
    return s.pop() if len(s) == 1 else "mixed"


def innermost(ops: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Time per class of ``(start, end, class)`` intervals, each instant
    given to the interval that started last (the innermost of nested
    operations); the total is the union of the intervals."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    bounds = sorted({t for s, e, _ in ops for t in (s, e)})
    out: Dict[str, float] = defaultdict(float)
    heap: list = []
    j = 0
    for t, t_next in zip(bounds, bounds[1:]):
        while j < len(ops) and ops[j][0] <= t:
            heapq.heappush(heap, (-ops[j][0], -j, ops[j][1], ops[j][2]))
            j += 1
        while heap and heap[0][2] <= t:
            heapq.heappop(heap)
        if heap:
            out[heap[0][3]] += t_next - t
    return out


def classes(dev: tr.Device, lo: float, hi: float,
            tables: Dict[str, Dict[str, Iterable[str]]]
            ) -> List[Tuple[float, float, str]]:
    """The device's operations in the window, clipped to it, each with
    its class; raises on an operation that no table names."""
    runs = sorted((s, e, module_key(n)) for n, s, e in dev.modules)
    starts = [r[0] for r in runs]
    out = []
    for name, s, e in dev.ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1]:
            raise ValueError(f"operation {name} at {s} ns runs in no "
                             f"program execution of the trace")
        module = runs[i][2]
        if module not in tables:
            raise ValueError(f"operation {name} runs in {module}, which "
                             f"is none of the step programs "
                             f"{sorted(tables)}")
        if name not in tables[module]:
            raise ValueError(f"operation {name} is not an instruction "
                             f"of {module}")
        out.append((s, e, classify(tables[module][name])))
    return out


def reduce(devs: List[tr.Device], lo: float, hi: float,
           tables: Dict[str, Dict[str, Iterable[str]]]) -> Dict[str, float]:
    """``{class: % of the window}``, a mean over ``devs``."""
    out = dict.fromkeys(CLASSES, 0.0)
    for d in devs:
        for c, t in innermost(classes(d, lo, hi, tables)).items():
            out[c] += 100.0 * t / (hi - lo) / len(devs)
    return out


def _program_tables():
    try:
        from repro.obs import phases
    except ImportError:
        return None
    return phases.step_programs() or None


def shares(ctx: dict) -> Optional[Dict[str, float]]:
    """The window's shares by class, reduced once per run; ``None``
    where the program names no phases.  The tables are those of the
    step programs this process ran, or a recorded run's, as
    :func:`to_json` writes them, in ``ctx["phase_tables"]``."""
    if "_phase_shares" not in ctx:
        tables = (from_json(ctx["phase_tables"]) if "phase_tables" in ctx
                  else _program_tables())
        ctx["_phase_shares"] = None if tables is None else reduce(
            ctx["trace_devices"], *ctx["window"], tables)
    return ctx["_phase_shares"]


def to_json(tables: Dict[str, Dict[str, Iterable[str]]]) -> dict:
    """``{module: {"backward+update": [instruction, ...], ...}}``: a
    table grouped by phase set, the empty set under ``""``."""
    out: dict = {}
    for module, t in tables.items():
        groups: Dict[str, list] = defaultdict(list)
        for name, ps in t.items():
            groups["+".join(sorted(ps))].append(name)
        out[module] = {k: sorted(v) for k, v in sorted(groups.items())}
    return out


def from_json(obj: dict) -> Dict[str, Dict[str, frozenset]]:
    return {module: {name: frozenset(k.split("+")) if k else frozenset()
                     for k, names in groups.items() for name in names}
            for module, groups in obj.items()}
