"""The reduction from a profiler trace to the phase shares
(``bench/phases.py``).

Checked on hand-made devices and tables, and on a run recorded on a
TPU v5e by ``bench/phase_breakdown.py --out`` (``tests/bench/data/``):
its trace, the step program's phase table and the values its readers
read.
"""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import cells, phases
from bench import trace as tr

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
METRICS = {"predict_pct": "predict", "update_pct": "update",
           "head_pct": "head", "phase_mixed_pct": "mixed"}

TABLES = {"jit_step": {
    "p.1": {"predict"}, "f.1": {"forward"}, "h.1": {"head"},
    "u.1": {"update"}, "m.1": {"forward", "predict"}, "x.1": set(),
    # a while loop whose body holds h.1 and m.1
    "w.1": {"forward", "head", "predict"}}}


def _device(shift=0.0):
    d = tr.Device()
    d.ops = [("p.1", 0, 10), ("f.1", 10, 30), ("w.1", 30, 60),
             ("h.1", 30, 40), ("m.1", 45, 55), ("u.1", 60, 70),
             ("x.1", 75, 80)]
    d.ops = [(n, s + shift, e + shift) for n, s, e in d.ops]
    d.modules = [("jit_step(1234)", shift, 80 + shift)]
    return d


def test_single_phase_and_mixed_shares():
    got = phases.reduce([_device()], 0.0, 100.0, TABLES)
    # w.1 keeps the 10 of its 30 that its body's ops leave
    assert got == {"predict": 10.0, "forward": 20.0, "head": 10.0,
                   "backward": 0.0, "update": 10.0, "transfer": 0.0,
                   "mixed": 20.0, "unscoped": 5.0}
    assert phases.classify(set()) == "unscoped"
    assert phases.classify({"head", "update"}) == "mixed"
    assert phases.module_key("jit_tick_fn(14378514057353209887)") \
        == "jit_tick_fn"


@pytest.mark.parametrize("lo,hi", [(0.0, 100.0), (5.0, 52.0),
                                   (33.0, 77.0)])
def test_shares_add_up_to_busy(lo, hi):
    d = _device()
    got = phases.reduce([d], lo, hi, TABLES)
    assert sum(got.values()) == pytest.approx(
        100.0 * tr.busy(d, lo, hi) / (hi - lo))


def test_an_op_no_table_names_raises():
    d = _device()
    d.ops.append(("fusion.9", 71, 72))
    with pytest.raises(ValueError, match="fusion.9 is not an instruction"):
        phases.reduce([d], 0.0, 100.0, TABLES)
    d = _device()
    d.modules = [("jit_other(5)", 0, 80)]
    with pytest.raises(ValueError, match="none of the step programs"):
        phases.reduce([d], 0.0, 100.0, TABLES)
    d = _device()
    d.modules = [("jit_step(1234)", 0, 50)]
    with pytest.raises(ValueError, match="no program execution"):
        phases.reduce([d], 0.0, 100.0, TABLES)


def test_readers_average_chips_and_are_silent_without_phases(monkeypatch):
    a, b = _device(), _device()
    b.ops = [("u.1", 0, 40)]
    ctx = {"window": (0.0, 100.0), "trace_devices": [a, b],
           "phase_tables": phases.to_json(TABLES)}
    assert phases.from_json(ctx["phase_tables"]) == TABLES
    got = {m: cells.reader(ROOT, m)(ctx) for m in METRICS}
    assert got == {"predict_pct": 5.0, "update_pct": 25.0,
                   "head_pct": 5.0, "phase_mixed_pct": 10.0}
    # a program that names no phases (the parent commit's) reads nothing
    monkeypatch.setattr(phases, "_program_tables", lambda: None)
    ctx = {"window": (0.0, 100.0), "trace_devices": [a]}
    assert {m: cells.reader(ROOT, m)(ctx) for m in METRICS} == \
        dict.fromkeys(METRICS)


def test_recorded_chip_run():
    """Every operation of the recorded window resolves through the
    recorded table; the classes add up to the busy share, the unscoped
    ones to less than a tenth of it; the readers give the run's values
    again."""
    want = json.loads((DATA / "granite8b-stream-1chip-scoped.json")
                      .read_text())
    t = tr.load(DATA / "granite8b-stream-1chip-scoped.xplane.pb")
    lo, hi = t.window()
    devs = [t.devices[i] for i in want["device_ids"]]
    ctx = {"window": (lo, hi), "trace_devices": devs, **want["context"]}
    got = phases.shares(ctx)
    busy = 100.0 * sum(tr.busy(d, lo, hi) for d in devs) \
        / len(devs) / (hi - lo)
    assert sum(got.values()) == pytest.approx(busy, abs=0.1)
    assert got["unscoped"] < 0.1 * busy
    assert got == pytest.approx(want["shares"], rel=1e-9, abs=1e-9)
    for name, key in METRICS.items():
        assert cells.reader(ROOT, name)(ctx) == pytest.approx(
            want["metrics"][name], rel=1e-9, abs=1e-9)
        assert want["metrics"][name] == got[key]
