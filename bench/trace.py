"""From a ``jax.profiler`` trace to per-device intervals.

The trace is the ``.xplane.pb`` the profiler writes.  Each chip is a
plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event
per operation that ran on the chip, named by its HLO text
(``%fusion.12 = bf16[...] fusion(%a, %b), ...``), and its ``XLA
Modules`` line one per program execution.  An operation is known by its
instruction name (``fusion.12``, ``collective-permute-done.3``) and
reported under a short label: that name, its opcode and its operands'
names.  The harness's own spans (``bench.window``,
``bench.step``, ``bench.data``, ``bench.dispatch``, ``bench.wait``) are
on the host plane.  Everything is reduced to sorted, merged intervals
in nanoseconds on the trace's one clock, clipped to the window span:

* busy: the union of a device's operation intervals;
* collective: the union of its collective-permute operations;
* compute: the union of every other operation;
* step gaps: the device time between one execution of the step's
  program (the module with the most device time) and the next in which
  nothing at all ran on the device;
* idle gaps: stretches of the window with no operation on the device,
  each labelled by the innermost harness span open on the host at its
  middle.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# matched against an operation's instruction name only: its HLO text
# also names its operands, and a fusion that reads a permute's result is
# compute
COLLECTIVE = re.compile(r"^(collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|send|recv)")
PERMUTE = re.compile(r"^collective-permute")
OPERAND = re.compile(r"%([\w.\-]+)")
OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
LABEL_CHARS = 200
SPAN_PREFIX = "bench."


def merge(iv: np.ndarray) -> np.ndarray:
    """Sorted, non-overlapping union of ``[n, 2]`` intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=float)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv
    iv = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return iv[iv[:, 1] > iv[:, 0]]


def total(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Parts of merged ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return np.asarray(out, dtype=float).reshape(-1, 2)


def op_name(text: str) -> str:
    """``fusion.12`` of ``%fusion.12 = ...``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_label(text: str) -> str:
    """``fusion.12 fusion(a, b)``: name, opcode and operand names."""
    head, _, rest = text.partition(" = ")
    m = OPCODE.search(rest)
    if not m:
        return head.lstrip("%")
    args = ", ".join(OPERAND.findall(rest[m.end():]))
    return f"{head.lstrip('%')} {m.group(1)}({args})"[:LABEL_CHARS]


@dataclass
class Device:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    labels: Dict[str, str] = field(default_factory=dict)

    def intervals(self, pattern=None, exclude=None) -> np.ndarray:
        iv = [(s, e) for n, s, e in self.ops
              if (pattern is None or pattern.search(n))
              and (exclude is None or not exclude.search(n))]
        return merge(np.asarray(iv, dtype=float).reshape(-1, 2))


@dataclass
class Trace:
    devices: Dict[int, Device]
    spans: List[Tuple[str, float, float]]   # harness spans on the host

    def window(self) -> Tuple[float, float]:
        w = [(s, e) for n, s, e in self.spans if n == "bench.window"]
        if not w:
            raise ValueError("the trace holds no bench.window span")
        return w[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[int, Device] = {}
    spans = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name = op_name(e.name)
                        dev.ops.append((name, e.start_ns, e.end_ns))
                        if name not in dev.labels:
                            dev.labels[name] = op_label(e.name)
                elif line.name == "XLA Modules":
                    dev.modules += [(e.name, e.start_ns, e.end_ns)
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(devices, sorted(spans, key=lambda s: s[1]))


def busy(dev: Device, lo: float, hi: float) -> float:
    return total(clip(dev.intervals(), lo, hi))


def exposed_permute(dev: Device, lo: float, hi: float) -> float:
    """Time in which a collective-permute runs and no compute does."""
    perm = clip(dev.intervals(PERMUTE), lo, hi)
    compute = clip(dev.intervals(exclude=COLLECTIVE), lo, hi)
    return total(subtract(perm, compute))


def step_module(dev: Device) -> Optional[str]:
    t: Dict[str, float] = defaultdict(float)
    for n, s, e in dev.modules:
        t[n] += e - s
    return max(t, key=t.get) if t else None


def step_gaps(dev: Device, lo: float, hi: float) -> float:
    """Device-idle time between consecutive executions of the step's
    program inside the window."""
    name = step_module(dev)
    runs = sorted((s, e) for n, s, e in dev.modules
                  if n == name and s >= lo and e <= hi)
    gaps = merge(np.asarray([(a[1], b[0]) for a, b in zip(runs, runs[1:])
                             if b[0] > a[1]], dtype=float).reshape(-1, 2))
    return total(subtract(gaps, dev.intervals()))


def idle_gaps(dev: Device, spans, lo: float, hi: float, n: int = 10):
    """The ``n`` longest idle stretches of the window, each as
    ``[label, seconds]``: the innermost harness span (other than
    window and step) open on the host at its middle."""
    idle = subtract(np.asarray([[lo, hi]], dtype=float),
                    clip(dev.intervals(), lo, hi))
    order = np.argsort(idle[:, 0] - idle[:, 1], kind="stable")[:n]
    out = []
    for s, e in idle[order]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]
                 and sp[0] not in ("bench.window", "bench.step")]
        label = max(open_, key=lambda sp: sp[1])[0] if open_ else "none"
        out.append([label.replace(SPAN_PREFIX, ""), (e - s) * 1e-9])
    return out


def top_ops(devices: Dict[int, Device], lo: float, hi: float, n: int = 10):
    """The ``n`` operations with the most device time in the window, as
    ``[label, seconds]`` averaged over the devices."""
    t: Dict[str, float] = defaultdict(float)
    for dev in devices.values():
        for name, s, e in dev.ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                t[dev.labels.get(name, name)] += (e - s) * 1e-9 / len(devices)
    return [[k, v] for k, v in sorted(t.items(), key=lambda kv: -kv[1])[:n]]
